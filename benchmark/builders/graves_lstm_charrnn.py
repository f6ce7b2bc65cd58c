"""Builder of the ``graves_lstm_charrnn`` configuration: ``layers`` x
GravesLSTM(``width``) + RnnOutputLayer over a ``vocab``-character alphabet,
truncated BPTT ``tbptt``, Adam 1e-3. Copied from ``bench.graves_lstm_net``
(which fixes the seed at 1 and the cache mode in code); it is not a zoo
class, so it is code of the benchmark's own."""
from __future__ import annotations


def build(seed, vocab, width, layers, tbptt):
    from deeplearning4j_tpu import Adam
    from deeplearning4j_tpu.nn.conf import BackpropType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer

    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(Adam(learning_rate=1e-3)).activation("tanh").list())
    n_in = vocab
    for _ in range(layers):
        b = b.layer(GravesLSTM(n_in=n_in, n_out=width))
        n_in = width
    conf = b.layer(RnnOutputLayer(n_in=width, n_out=vocab,
                                  activation="softmax", loss="mcxent")).build()
    conf.backprop_type = BackpropType.TruncatedBPTT
    conf.tbptt_fwd_length = tbptt
    conf.tbptt_back_length = tbptt
    return conf
