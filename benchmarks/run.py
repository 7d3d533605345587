"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Brings the server up on this machine's chip, offers the cell's traffic
for ``--seconds``, and prints as the last line of standard output one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(``--trace 0``: the cell's end-to-end metrics; ``--trace 1``: its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and
last ``compared``: every number that decided ``correct`` beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.

Nothing here names a cell, a configuration or a traffic mix: see
``benchmarks/README.md`` for the files a new cell brings.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import compare, spec, xplane  # noqa: E402
from benchmarks.harness.loadgen import LoadGen, now  # noqa: E402
from benchmarks.harness.series import RunRecord  # noqa: E402
from benchmarks.harness.traffic import Plan  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
SAMPLE_REQUESTS = 8         # served requests the reference goes over
DRAIN_S = 60.0              # wait this long past the close for answers


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find_device(chips: int, require_tpu: bool) -> dict:
    """What JAX found; raises unless it is a TPU with enough chips."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and (dev["platform"] != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} x {dev['platform']}: nothing was run")
    return dev


def set_up_compile_cache() -> None:
    """The program's own fixed directory (``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_compile_cache``), keeping every program,
    however quick its compile."""
    import jax

    from brpc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCount:
    """Backend compilations, from JAX's own monitoring events: the
    window must see none."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def log_memory(when: str) -> None:
    import jax

    stats = jax.local_devices()[0].memory_stats()
    if stats:
        log(f"memory {when}: in use {stats['bytes_in_use'] / 1e9:.2f} GB, "
            f"peak {stats['peak_bytes_in_use'] / 1e9:.2f} GB of "
            f"{stats.get('bytes_limit', 0) / 1e9:.2f} GB")


def log_window(run: RunRecord, due: list) -> None:
    """What the window did, on standard error: for the one who reads a
    run that went wrong, or looks for a knee."""
    itl = sorted(run.series("itl_ms")) or [float("nan")]
    log(f"window {run.t1 - run.t0:.3f}s: "
        f"{run.tokens_between(run.t0, run.t1)} tokens, "
        f"{run.delta(['kv', 'steps'])} steps, "
        f"{run.delta(['kv', 'prefills_run'])} prefills, {len(due)} requests "
        f"due; gap between tokens p50 {itl[len(itl) // 2]:.3f} ms, "
        f"mean {sum(itl) / len(itl):.3f} ms, max {itl[-1]:.1f} ms")
    mid = (run.t0 + run.t1) / 2
    halves = [sorted((r.stamps[0] - r.due) * 1e3 for r in due
                     if r.stamps and (r.due < mid) == first)
              for first in (True, False)]
    still = sum(1 for r in due if r.closed is None or r.closed > run.t1)
    log("first token after p50, by halves of the window: "
        + " / ".join(f"{h[len(h) // 2]:.1f} ms" if h else "-" for h in halves)
        + f"; {still} streams open at the close")


def wait_for_warmup(gen: LoadGen, warm: dict, t_begin: float) -> None:
    """The traffic runs; the window opens when the mix's warm-up is
    done: so many sessions or requests closed, or so many seconds."""
    deadline = t_begin + 300.0
    while now() < deadline:
        if gen.error is not None:
            raise gen.error
        if (gen.closed_sessions >= warm.get("sessions", 0)
                and gen.closed_requests >= warm.get("requests", 0)
                and now() - t_begin >= warm.get("seconds", 0.0)):
            return
        time.sleep(0.002)
    raise RuntimeError("the mix's warm-up did not finish in 300 s")


def traced_window(served, t0: float, seconds: float, mix: dict) -> dict:
    """Profile ``trace_s`` seconds of the window, from ``trace_at_s``
    into it; sleep out the rest.  Returns the host's clock and the
    batcher's step count at both ends, and the trace's reduction."""
    import jax

    at = float(mix.get("trace_at_s", 2.0))
    length = min(float(mix.get("trace_s", 4.0)), max(seconds - at - 1.0, 0.5))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    time.sleep(max(0.0, t0 + at - now()))
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    steps_a, ta = served.steps_run(), now()
    time.sleep(length)
    steps_b, tb = served.steps_run(), now()
    jax.profiler.stop_trace()
    time.sleep(max(0.0, t0 + seconds - now()))
    return {"ta": ta, "tb": tb, "steps_a": steps_a, "steps_b": steps_b,
            "path": xplane.find_xplane(TRACE_DIR)}


def measure(metrics: list, run: RunRecord) -> dict:
    """Each metric through the reader its file names.  A reader that
    finds nothing to read returns nothing and the metric is left out."""
    out = {}
    for m in metrics:
        if m["name"] == "setup_s":
            value = run.setup_s
        else:
            if "reader" not in m["spec"]:
                raise RuntimeError(f"metric {m['name']} has no file under "
                                   "benchmarks/metrics/")
            reader = spec.load_module("readers", m["spec"]["reader"])
            value = reader.read(run, m["spec"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class Window:
    """What set-up and the window left behind, before the comparison:
    the result's line so far, the sample of served requests, and what
    the comparison needs to go over it."""

    def __init__(self, cell, run, result, sample, params, unfinished,
                 compiled_in_window):
        self.cell, self.run, self.result = cell, run, result
        self.sample, self.params = sample, params
        self.unfinished = unfinished
        self.compiled_in_window = compiled_in_window

    def reference(self, **kw):
        return self.cell.model.Reference(self.cell.config, self.params, **kw)

    def judged(self, got: dict) -> dict:
        """The result line with ``correct`` decided from ``got``
        (``compare.compare``'s readings), ``compared`` its last key."""
        correct, compared = compare.judge(
            got, self.cell.config["correct"], self.unfinished,
            self.compiled_in_window)
        return {"correct": correct, **self.result, "compared": compared}


def run_window(cell: spec.Cell, seed: int, seconds: float, trace: bool,
               require_tpu: bool = True, t_start: float = None) -> Window:
    """Set up, measure, free the program's state, pick the sample."""
    import jax

    from benchmarks.harness import serving
    from benchmarks.harness.peaks import device_peaks

    t_start = now() if t_start is None else t_start
    device = find_device(cell.chips, require_tpu)
    on_tpu = device["platform"] == "tpu"
    peaks = device_peaks(device["kind"]) if on_tpu else None
    set_up_compile_cache()
    compiles = CompileCount()
    cfg, mix, model = cell.config, cell.traffic, cell.model
    log(f"set-up: {device['count']} x {device['kind']} found after "
        f"{now() - t_start:.1f}s")

    # -- set-up: weights, server, every shape the mix reaches -------------
    params = model.make_params(cfg, seed)
    jax.block_until_ready(params)
    log(f"set-up: weights made after {now() - t_start:.1f}s")
    log_memory("after the weights")
    served = serving.Served(model.make_service(cfg, params))
    log(f"set-up: server up after {now() - t_start:.1f}s")
    plan = Plan(mix, seed, cfg["vocab_size"])
    try:
        LoadGen(served, plan, truncate_to=1).play(plan.block(), 600.0)
        log(f"set-up: shapes warm after {now() - t_start:.1f}s "
            f"({compiles.n} compiled or loaded)")
        log_memory("after the pass over the strata")
        gen = LoadGen(served, plan)
        t_begin = now()
        gen.start()
        wait_for_warmup(gen, mix.get("warmup", {}), t_begin)

        # -- the window ----------------------------------------------------
        n_compiled = compiles.n
        c0, t0 = served.counters(), now()
        setup_s = t0 - t_start
        tr = None
        if trace and on_tpu:
            tr = traced_window(served, t0, seconds, mix)
        else:
            time.sleep(max(0.0, t0 + seconds - now()))
        c1, t1 = served.counters(), now()
        gen.stop()
        never = gen.drain(DRAIN_S)
        compiled_in_window = compiles.n - n_compiled
        stats = [d.memory_stats() for d in jax.local_devices()]
        mem_peak = max((s["peak_bytes_in_use"] for s in stats if s),
                       default=None)
    finally:
        served.stop()
    del served
    held = serving.free_program_state(params)
    log(f"the program's state let go: {held / 1e9:.2f} GB still in use")
    if tr is not None:
        tr["reduced"] = xplane.reduce_trace(tr["path"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    run = RunRecord(cell=cell, seconds=seconds, t0=t0, t1=t1,
                    requests=gen.requests, c0=c0, c1=c1, trace=tr,
                    peaks=peaks, mem_peak_bytes=mem_peak, setup_s=setup_s)
    due = run.due_in_window()
    log_window(run, due)
    log_memory("after the window")
    bad = [r for r in due if r.reason != "finished"
           or len(r.tokens) != r.max_new]
    dev = dict(device)
    dev["memory_peak_bytes"] = mem_peak
    result = {"attempted": len(due), "failed": len(bad),
              "metrics": measure(cell.per_layer if trace
                                 else cell.end_to_end, run),
              "device": dev}
    if tr is not None:
        red = tr["reduced"]
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    finished = [r for r in due if r not in bad]
    return Window(cell, run, result,
                  compare.pick_sample(finished, seed, SAMPLE_REQUESTS),
                  params, len(bad) + never, compiled_in_window)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float = None) -> dict:
    """One run: the window, then ``correct``: the configuration's plain
    reference over a sample of what the window served.  Returns the
    result line's object."""
    win = run_window(cell, seed, seconds, trace, require_tpu, t_start)
    t_ref = now()
    got = compare.compare(win.reference(), win.sample)
    log(f"set-up {win.run.setup_s:.1f}s; reference: "
        f"{got['requests_compared']} requests, {got['tokens_compared']} "
        f"tokens in {now() - t_ref:.1f}s")
    return win.judged(got)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "brpc_tpu")):
        raise SystemExit("the program (brpc_tpu/) is not in this checkout: "
                         "nothing was run")
    cell = spec.Cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=_T_START)
    for name, c in result["compared"].items():
        log(f"compared {name}: {json.dumps(c)}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
