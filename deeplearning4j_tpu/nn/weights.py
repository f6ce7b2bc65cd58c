"""Weight initialization schemes.

TPU-native equivalent of the reference's ``WeightInit`` enum and ``WeightInitUtil``
(reference ``deeplearning4j-nn/src/main/java/org/deeplearning4j/nn/weights/WeightInit.java``,
``WeightInitUtil.java``). Uses ``jax.random`` PRNG keys (counter-based, reproducible
across device meshes) instead of ND4J's global RNG.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["WeightInit", "Distribution", "NormalDistribution", "UniformDistribution",
           "init_weight"]


def _np_rng(rng):
    """Host numpy Generator deterministically seeded from a jax PRNG key, or
    None when the key is a tracer (init under jit keeps the jax.random path).

    Why host sampling: eager ``jax.random.normal`` compiles one tiny XLA
    program PER DISTINCT SHAPE. GoogLeNet's 57 convs have ~50 distinct
    weight shapes → ~170 device compiles before training even starts (70 s
    of an 81 s init on CPU — the 'GoogLeNet first-compile blowup' was
    mostly THIS). numpy sampling is
    exact-deterministic from the same key and costs zero compiles."""
    if isinstance(rng, jax.core.Tracer):
        return None
    arr = np.asarray(jax.random.key_data(rng)
                     if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key)
                     else rng).ravel()
    return np.random.default_rng([int(x) for x in arr])


class _InitClock(threading.local):
    """What the eager initialisers below spent on this thread since
    :func:`reset_init_clock`: seconds making arrays on the host
    (``draw_s``: seeding, sampling, filling), seconds in the
    ``jnp.asarray`` that hands each to the device (``place_s``), and how
    many arrays (``leaves``). A network's ``init()`` resets it and reads
    it into its ``init/params`` span (``nn/training.py``): two sums, and
    no span per leaf (ResNet50 has some five hundred)."""
    draw_s = 0.0
    place_s = 0.0
    leaves = 0


_CLOCK = _InitClock()


def reset_init_clock():
    _CLOCK.draw_s, _CLOCK.place_s, _CLOCK.leaves = 0.0, 0.0, 0


def read_init_clock():
    return {"draw_s": _CLOCK.draw_s, "place_s": _CLOCK.place_s,
            "leaves": _CLOCK.leaves}


def _place(out, t0):
    """``jnp.asarray(out)`` for a host array made since ``t0``, both parts
    of the time added to this thread's :class:`_InitClock`."""
    t1 = time.perf_counter()
    arr = jnp.asarray(out)
    t2 = time.perf_counter()
    _CLOCK.draw_s += t1 - t0
    _CLOCK.place_s += t2 - t1
    _CLOCK.leaves += 1
    return arr


#: elements ``_normal`` samples at a time (1 MB of float64)
_CHUNK = 1 << 17


def _normal(rng, shape, dtype, scale=1.0, shift=0.0):
    """Sampling, scaling and shifting all happen host-side in the eager path:
    an eager device multiply/add would compile one tiny program per distinct
    shape, re-creating the init blowup _np_rng exists to kill."""
    t0 = time.perf_counter()
    g = _np_rng(rng)
    if g is None:
        return jax.random.normal(rng, shape, dtype) * scale + shift
    # a cache-sized piece at a time, in place, the same stream and the same
    # float64 arithmetic as one call over the whole shape: drawn whole, a
    # leaf of 100M elements (a 49,152-word embedding) goes through main
    # memory in three float64 temporaries of its size
    out = np.empty(shape, np.dtype(dtype))
    flat = out.reshape(-1)
    buf = np.empty(min(_CHUNK, flat.size))
    for i in range(0, flat.size, _CHUNK):
        part = flat[i:i + _CHUNK]
        draw = buf[:part.size]
        g.standard_normal(out=draw)
        draw *= scale
        draw += shift
        part[...] = draw
    return _place(out, t0)


def _uniform(rng, shape, dtype, lo, hi):
    t0 = time.perf_counter()
    g = _np_rng(rng)
    if g is None:
        return jax.random.uniform(rng, shape, dtype, lo, hi)
    return _place(g.uniform(lo, hi, size=shape).astype(dtype), t0)


def host_full(shape, value, dtype):
    """Eager constant init without an XLA compile: numpy fill + device_put.
    (Eager ``jnp.full``/``jnp.zeros`` compiles a tiny program per distinct
    shape — see ``_np_rng``.)"""
    t0 = time.perf_counter()
    return _place(np.full(shape, value, dtype=np.dtype(dtype)), t0)


class WeightInit:
    DISTRIBUTION = "distribution"
    ZERO = "zero"
    ONES = "ones"
    CONSTANT = "constant"
    SIGMOID_UNIFORM = "sigmoid_uniform"
    NORMAL = "normal"
    LECUN_NORMAL = "lecun_normal"
    LECUN_UNIFORM = "lecun_uniform"
    UNIFORM = "uniform"
    XAVIER = "xavier"
    XAVIER_UNIFORM = "xavier_uniform"
    XAVIER_FAN_IN = "xavier_fan_in"
    XAVIER_LEGACY = "xavier_legacy"
    RELU = "relu"
    RELU_UNIFORM = "relu_uniform"
    IDENTITY = "identity"
    VAR_SCALING_NORMAL_FAN_IN = "var_scaling_normal_fan_in"
    VAR_SCALING_NORMAL_FAN_OUT = "var_scaling_normal_fan_out"
    VAR_SCALING_NORMAL_FAN_AVG = "var_scaling_normal_fan_avg"
    VAR_SCALING_UNIFORM_FAN_IN = "var_scaling_uniform_fan_in"
    VAR_SCALING_UNIFORM_FAN_OUT = "var_scaling_uniform_fan_out"
    VAR_SCALING_UNIFORM_FAN_AVG = "var_scaling_uniform_fan_avg"


@dataclasses.dataclass
class Distribution:
    """Base for WeightInit.DISTRIBUTION (reference ``nn/conf/distribution/``)."""

    def sample(self, rng, shape, dtype):  # pragma: no cover - abstract
        raise NotImplementedError

    def to_dict(self):
        d = dataclasses.asdict(self)
        d["@dist"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d):
        d = dict(d)
        kind = d.pop("@dist")
        cls = {c.__name__: c for c in (NormalDistribution, UniformDistribution,
                                       GaussianDistribution, ConstantDistribution,
                                       BinomialDistribution)}[kind]
        return cls(**d)


@dataclasses.dataclass
class NormalDistribution(Distribution):
    mean: float = 0.0
    std: float = 1.0

    def sample(self, rng, shape, dtype):
        return _normal(rng, shape, dtype, scale=self.std, shift=self.mean)


# Reference has both GaussianDistribution and NormalDistribution (synonyms).
@dataclasses.dataclass
class GaussianDistribution(NormalDistribution):
    pass


@dataclasses.dataclass
class UniformDistribution(Distribution):
    lower: float = -1.0
    upper: float = 1.0

    def sample(self, rng, shape, dtype):
        return _uniform(rng, shape, dtype, self.lower, self.upper)


@dataclasses.dataclass
class ConstantDistribution(Distribution):
    value: float = 0.0

    def sample(self, rng, shape, dtype):
        return host_full(shape, self.value, dtype)


@dataclasses.dataclass
class BinomialDistribution(Distribution):
    trials: int = 1
    p: float = 0.5

    def sample(self, rng, shape, dtype):
        g = _np_rng(rng)
        if g is None:
            return jax.random.binomial(rng, self.trials, self.p,
                                       shape).astype(dtype)
        return jnp.asarray(g.binomial(self.trials, self.p,
                                      size=shape).astype(dtype))


def init_weight(rng, shape, fan_in, fan_out, scheme=WeightInit.XAVIER,
                dist: Optional[Distribution] = None, dtype=jnp.float32):
    """Initialize one weight tensor.

    Formulas match reference ``WeightInitUtil.initWeights`` (e.g. XAVIER =
    N(0, 2/(fanIn+fanOut)), RELU = N(0, 2/fanIn), SIGMOID_UNIFORM =
    U(±4·sqrt(6/(fanIn+fanOut)))).
    """
    scheme = str(scheme).lower()
    fan_in = max(float(fan_in), 1.0)
    fan_out = max(float(fan_out), 1.0)

    if scheme == WeightInit.DISTRIBUTION:
        if dist is None:
            raise ValueError("WeightInit.DISTRIBUTION requires a Distribution")
        return dist.sample(rng, shape, dtype)
    if scheme == WeightInit.ZERO:
        return host_full(shape, 0, dtype)
    if scheme == WeightInit.ONES:
        return host_full(shape, 1, dtype)
    if scheme == WeightInit.IDENTITY:
        if len(shape) != 2 or shape[0] != shape[1]:
            raise ValueError("IDENTITY init requires a square 2-D shape")
        return jnp.asarray(np.eye(shape[0], dtype=np.dtype(dtype)))
    if scheme == WeightInit.NORMAL:
        return _normal(rng, shape, dtype, scale=1.0 / math.sqrt(fan_in))
    if scheme == WeightInit.LECUN_NORMAL:
        return _normal(rng, shape, dtype, scale=math.sqrt(1.0 / fan_in))
    if scheme == WeightInit.UNIFORM:
        a = math.sqrt(1.0 / fan_in)
        return _uniform(rng, shape, dtype, -a, a)
    if scheme == WeightInit.LECUN_UNIFORM:
        a = math.sqrt(3.0 / fan_in)
        return _uniform(rng, shape, dtype, -a, a)
    if scheme == WeightInit.XAVIER:
        return _normal(rng, shape, dtype,
                       scale=math.sqrt(2.0 / (fan_in + fan_out)))
    if scheme == WeightInit.XAVIER_UNIFORM:
        a = math.sqrt(6.0 / (fan_in + fan_out))
        return _uniform(rng, shape, dtype, -a, a)
    if scheme == WeightInit.XAVIER_FAN_IN:
        return _normal(rng, shape, dtype, scale=1.0 / math.sqrt(fan_in))
    if scheme == WeightInit.XAVIER_LEGACY:
        return _normal(rng, shape, dtype,
                       scale=math.sqrt(1.0 / (fan_in + fan_out)))
    if scheme == WeightInit.RELU:
        return _normal(rng, shape, dtype, scale=math.sqrt(2.0 / fan_in))
    if scheme == WeightInit.RELU_UNIFORM:
        a = math.sqrt(6.0 / fan_in)
        return _uniform(rng, shape, dtype, -a, a)
    if scheme == WeightInit.SIGMOID_UNIFORM:
        a = 4.0 * math.sqrt(6.0 / (fan_in + fan_out))
        return _uniform(rng, shape, dtype, -a, a)
    if scheme.startswith("var_scaling"):
        if scheme.endswith("fan_in"):
            denom = fan_in
        elif scheme.endswith("fan_out"):
            denom = fan_out
        else:
            denom = 0.5 * (fan_in + fan_out)
        if "normal" in scheme:
            return _normal(rng, shape, dtype, scale=math.sqrt(1.0 / denom))
        a = math.sqrt(3.0 / denom)
        return _uniform(rng, shape, dtype, -a, a)
    raise ValueError(f"Unknown weight init scheme '{scheme}'")
