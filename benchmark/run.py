"""One cell, one run:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell from its files (``BENCHMARK.json`` → ``configs/``,
``traffic/``), warms its shapes, measures one window through the program's
own ``fit``, checks the outcome outside the window and prints, last, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, traced, ``breakdown``. Untraced the metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, each from the reader of
its name under ``layer_metrics/``.

It runs only on a TPU with at least the cell's chips. ``--rehearse`` is the
one way past that check: the same control flow at the sizes the files'
``rehearse`` blocks give, on whatever JAX has, stamped with that platform;
its numbers are not measurements.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: where a traced run keeps its profile, inside the checkout (git-ignored)
TRACE_DIR = ".bench_trace"


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


class Entry:
    """The program's entry point a cell trains through: ``net.fit`` or
    ``ParallelWrapper.fit`` over ``chips`` devices (AVERAGING, frequency 1:
    one jitted step with a gradient all-reduce)."""

    def __init__(self, cell, net):
        self.net = net
        self.wrapper = None
        self.placed = []      # the DataSets train() put on the device itself
        if cell.traffic["entry"] == "parallel_wrapper":
            from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                                     TrainingMode)
            self.wrapper = (ParallelWrapper.Builder(net).workers(cell.chips)
                            .training_mode(TrainingMode.AVERAGING)
                            .averaging_frequency(1).build())
        elif cell.traffic["entry"] != "fit":
            raise SystemExit(f"entry {cell.traffic['entry']!r}: want fit or "
                             f"parallel_wrapper")

    def fit(self, iterator):
        import jax
        with jax.profiler.TraceAnnotation("bench/fit"):
            (self.wrapper or self.net).fit(iterator)

    def barrier(self):
        import jax
        jax.block_until_ready((self.net.params, self.net.score_))

    def release(self):
        """The run is over: the device arrays it held go, whoever still
        refers to them: the resident pool's or the wrapper's sharded
        batches, the network's parameters, layer states, updater state and
        last loss. The compiled programs stay: the per-layer readers map op
        names through their text."""
        from benchmark import device
        if self.wrapper is not None:
            self.wrapper.clear_device_cache()
        net = self.net
        device.delete(([ds.device_arrays() for ds in self.placed],
                       net.params, net.states, net.updater_state, net.score_))


def train(cell, seed, note):
    """Build the cell, warm it, measure nothing yet: returns what the window
    needs. Everything here is set-up."""
    from deeplearning4j_tpu.monitor.jitwatch import wait_cost_captures

    from benchmark import cells
    from benchmark.feed import Feed

    t = time.perf_counter()
    net = cells.build_net(cell, seed)
    note(f"network built in {time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    pool = cells.make_batches(cell.config, seed, int(cell.traffic["pool"]),
                              cell.batch, cell.seq_len)
    entry = Entry(cell, net)
    if cell.traffic["feed"] == "resident" and entry.wrapper is None:
        for ds in pool:              # the resident data set goes up once
            ds.device_arrays()
        entry.placed = pool
    note(f"pool of {len(pool)} batches made"
         f"{' and placed' if cell.traffic['feed'] == 'resident' else ''} in "
         f"{time.perf_counter() - t:.2f}s")
    feed = Feed(pool, lambda: net.score_, int(cell.traffic["run_ahead"]),
                group=cell.group)
    losses, took = [], []
    for _ in range(int(cell.traffic["warmup_batches"])):
        t = time.perf_counter()
        feed.arm(batches=cell.group)
        entry.fit(feed)
        losses.append(float(net.score_))
        took.append(round(time.perf_counter() - t, 2))
    entry.barrier()
    t = time.perf_counter()
    if not wait_cost_captures(timeout=600.0):
        raise SystemExit("jitwatch cost capture still running after 600 s")
    note(f"warm-up steps took {took}s, then {time.perf_counter() - t:.2f}s "
         f"for jitwatch's cost capture; losses "
         f"{[round(v, 4) for v in losses]}")
    return net, entry, feed, losses


def measure(cell, entry, feed, counters, seconds=None, batches=None):
    """One window through ``fit``, closed by ``block_until_ready`` on the
    parameters and the last loss."""
    from benchmark.counters import delta

    net = entry.net
    entry.barrier()
    before = counters.snapshot()
    it0, handed0, brake0 = net.iteration_count, feed.handed, \
        feed.barrier_seconds
    feed.arm(seconds=seconds, batches=batches)
    t0 = time.perf_counter()
    entry.fit(feed)
    entry.barrier()
    t1 = time.perf_counter()
    handed = feed.handed - handed0
    return types.SimpleNamespace(
        t0=t0, seconds=t1 - t0, batches=handed, steps=handed // cell.group,
        iterations=net.iteration_count - it0,
        units=handed * cell.units_per_batch,
        rate_per_chip=handed * cell.units_per_batch / (t1 - t0) / cell.chips,
        brake_seconds=feed.barrier_seconds - brake0,
        counters=delta(counters.snapshot(), before))


def traced(cell, entry, feed, counters, root):
    """A short window of its own under the profiler; returns the window and
    the trace."""
    import jax

    from benchmark import xplane

    out = os.path.join(root, TRACE_DIR, cell.name)
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # host events of JAX and ours only
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        win = measure(cell, entry, feed, counters,
                      batches=int(cell.traffic["trace_batches"]) * cell.group)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise SystemExit(f"the profiler left no .xplane.pb under {out}")
    return win, xplane.load(found[0])


def one_device(cell, seed, counters, seconds, note):
    """The same cell on device 0 alone, for a data-parallel one: the losses
    of its warm-up steps and its rate over ``seconds``."""
    alone = dataclasses.replace(
        cell, chips=1, traffic={**cell.traffic, "entry": "fit"})
    _, entry, feed, losses = train(alone, seed, note)
    win = measure(alone, entry, feed, counters, seconds=seconds)
    entry.release()              # or its network lingers on device 0
    note(f"one device alone: {win.steps} steps in {win.seconds:.2f}s, "
         f"{win.rate_per_chip:.1f} {cell.config['unit']}/s")
    return losses, win.rate_per_chip


@contextlib.contextmanager
def _monitor_off():
    """With the monitor on (its default) the fit loops fetch every step's
    loss at most two steps late (``monitor.StepCompletions``, lag 2, never
    the newest step's) and write its metrics; with a listener attached they
    fetch it at once. The window has neither: nothing is fetched."""
    import deeplearning4j_tpu.monitor as monitor

    was = monitor.enabled()
    monitor.set_enabled(False)
    try:
        yield
    finally:
        monitor.set_enabled(was)


def run_cell(manifest, root, workload, seed, seconds, trace, rehearse=False,
             note=log, trace_root=None):
    """Run one cell once; returns the result line's object. ``root`` holds
    the manifest's files; the profile of a traced run goes under
    ``trace_root`` (``root`` unless given). Set-up counts from this call:
    the program's imports, weights, pool, compile or cache load, warm-up."""
    t_setup = time.perf_counter()
    import jax
    from deeplearning4j_tpu.compilecache import enable

    from benchmark import cells, correct, device, xplane
    from benchmark.counters import Counters, window_compiles

    cell = cells.load_cell(manifest, root, workload, rehearse)
    devices = jax.devices()
    dev = device.describe(devices)
    if dev["platform"] != "tpu" and not rehearse:
        raise SystemExit(f"benchmark: jax found platform {dev['platform']!r} "
                         f"({dev['count']}x {dev['kind']}), not a TPU: "
                         f"nothing ran")
    if dev["count"] < cell.chips:
        raise SystemExit(f"benchmark: {workload} needs {cell.chips} chips, "
                         f"jax found {dev['count']}")
    # where JAX_COMPILATION_CACHE_DIR is set the cache lives there; without
    # it at this fixed path of the checkout (the path is part of the key).
    # A rehearsal's CPU programs stay out of it.
    cache_dir = None if rehearse else enable(os.path.join(root, ".jax_cache"))
    counters = Counters().install()
    note(f"benchmark: {workload} on {dev['count']}x {dev['kind']}, seed "
         f"{seed}, compile cache {cache_dir}")
    unit = cell.config["unit"]

    with _monitor_off():
        net, entry, feed, warm_losses = train(cell, seed, note)
        setup = counters.snapshot()
        setup_s = time.perf_counter() - t_setup

        win = measure(cell, entry, feed, counters, seconds=seconds)
        memory_peak = device.memory_peak_bytes(devices)   # before the checks
        note(f"window: {win.steps} steps ({win.units} {unit}) in "
             f"{win.seconds:.3f}s of {seconds}s asked, "
             f"{win.rate_per_chip:.1f} {unit}/s/chip; fit waited "
             f"{win.counters['input_wait_seconds']:.3f}s for input, of which "
             f"the run-ahead brake held {win.brake_seconds:.3f}s")

        run = types.SimpleNamespace(
            cell=cell, window=win, setup=setup, setup_s=setup_s,
            devices=devices[:cell.chips], trace=None, trace_window=None,
            extras={},
            peaks=device.peaks(dev["kind"]) if dev["platform"] == "tpu"
            else None,
            opcount=cells.module("opcount", cell.config["opcount"])
            if cell.config.get("opcount") else None)

        t_check = time.perf_counter()
        sharded = entry.wrapper is not None
        checks = [
            ("iterations",) + _iterations(cell, win),
            ("window_compiles", window_compiles(win.counters) == 0,
             f"{win.counters['jit_compiles']} jit compiles, "
             f"{win.counters['cache_requests']} cache requests and "
             f"{win.counters['backend_compiles']} backend compiles inside "
             f"the window"),
            ("state",) + correct.state(net, run.devices, dev["platform"],
                                       sharded)]
        if sharded:
            checks.append(("all_reduce",) + correct.holds_collective(
                device.live_program_texts(devices)))
        if trace:
            run.trace_window, run.trace = traced(cell, entry, feed, counters,
                                                 trace_root or root)
            note(f"traced window: {run.trace_window.steps} steps in "
                 f"{run.trace_window.seconds:.3f}s under the profiler, "
                 f"{run.trace_window.rate_per_chip:.1f} {unit}/s/chip "
                 f"(untraced window: {win.rate_per_chip:.1f})")
            if sharded:
                one_losses, run.extras["one_device_rate"] = one_device(
                    cell, seed, counters,
                    float(cell.traffic["one_device_seconds"]), note)
                checks.append(("trajectory",) + correct.trajectory(
                    warm_losses, one_losses))
        reference = (cells.module("reference", cell.config["reference"])
                     if cell.config.get("reference") else None)
        if reference is not None:
            spec = cell.config["correct_sample"]
            sample = cells.make_batches(cell.config, seed + 1, 1,
                                        int(spec["examples"]),
                                        spec.get("seq_len"))[0]
            # no check below needs the timed network: it leaves the device
            # before the check's own arrives, so that the check holds one
            # network at a time (memory_peak is read, the window is over)
            held = device.bytes_in_use(devices)
            entry.release()
            note(f"the timed run's arrays deleted: {held} -> "
                 f"{device.bytes_in_use(devices)} bytes in use on the device")
            fresh = cells.build_net(cell, seed)   # its own seeded weights
            checks.append(("reference",) + correct.against_reference(
                fresh, reference, sample, fresh.gc.compute_dtype))
    for name, ok, detail in checks:
        note(f"check {name}: {'ok' if ok else 'FAILED'} - {detail}")
    print(f"{workload}: {win.units} {unit} in {win.seconds:.4f}s on "
          f"{cell.chips} chip(s); correctness checks took "
          f"{time.perf_counter() - t_check:.2f}s (outside the window and "
          f"outside setup_s)", flush=True)

    state_ok = next(ok for name, ok, _ in checks if name == "state")
    result = {
        "correct": all(ok for _, ok, _ in checks),
        "attempted": win.steps,
        "failed": 0 if state_ok else win.steps,
        "metrics": _metrics(run, trace),
        "device": dict(dev, memory_peak_bytes=memory_peak),
    }
    if trace:
        span = xplane.window(run.trace)   # None off the chip: no device plane
        result["device"]["busy_s"] = xplane.busy_seconds(run.trace)
        result["device"]["window_s"] = (
            (span[1] - span[0]) * 1e-9 if span else run.trace_window.seconds)
        result["breakdown"] = {"device_ops": xplane.top_ops(run.trace),
                               "idle_gaps": xplane.idle_gaps(run.trace)}
    return result


def _iterations(cell, win):
    want = win.steps * cell.iterations_per_step
    ok = win.iterations == want and win.batches == win.steps * cell.group
    return ok, (f"{win.batches} batches handed out, {win.steps} steps, "
                f"iteration_count moved by {win.iterations} (expected {want})")


def _metrics(run, trace):
    """``{name: {"value", "unit"}}``: untraced the end-to-end metrics,
    traced the per-layer ones, each from its reader; a reader that finds
    nothing to read leaves its metric out."""
    from benchmark import cells

    out = {}
    if not trace:
        for entry in run.cell.metrics["end_to_end"]:
            value = (run.setup_s if entry["name"] == "setup_s"
                     else run.window.rate_per_chip)
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
        return out
    for entry in run.cell.metrics["per_layer"]:
        reader = cells.module("layer_metrics", entry["name"])
        if reader is None:
            raise SystemExit(f"no reader layer_metrics/{entry['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the control flow at rehearsal size on whatever "
                         "platform JAX has; not a measurement")
    args = ap.parse_args(argv)
    # the checkout's root, not this directory, leads the module path
    sys.path[:] = [ROOT] + [p for p in sys.path
                            if os.path.abspath(p or ".") != HERE]
    # The interpreter, JAX and the TPU runtime come up first and are not
    # set-up: 8-12 s that no change to this repo moves and that carried all
    # of set-up's spread (2-12 % against under 1 % for the rest; PERF.md).
    import jax
    jax.devices()
    log(f"platform start-up {time.perf_counter() - T_PROCESS:.2f}s (the "
        f"interpreter, JAX, the TPU runtime; before setup_s)")
    from benchmark import cells
    result = run_cell(cells.load_manifest(ROOT), ROOT, args.workload,
                      args.seed, args.seconds, bool(args.trace),
                      rehearse=args.rehearse)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
