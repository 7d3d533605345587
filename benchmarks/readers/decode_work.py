"""The decode step against what the model needs for the tokens it
produced, over the traced window.

spec: ``{"programs": ["jit_step"], "kind": "mfu" | "roofline"}``.

The host counts the steps it saw between the trace's start and stop
(the batcher's ``steps``) and knows every token received in between and
how many positions it attended over; the trace gives the device time of
every execution of the step program.  Work per step (host; counted by
the configuration's model module, ``step_work``) over device time per
step (trace):

- ``mfu``: needed FLOPs / device seconds / the chip's bf16 peak;
- ``roofline``: the least time the chip could take for the step (the
  larger of FLOPs / peak and bytes / bandwidth) over the device time.
"""
from benchmarks.harness.peaks import least_seconds


def read(run, spec):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    durs = run.program_durations(spec["programs"])
    steps = tr["steps_b"] - tr["steps_a"]
    lives = run.decoded_between(tr["ta"], tr["tb"])
    if not durs or not steps or not lives:
        return None
    dev_s = sum(durs) / len(durs)                 # device time a step
    flops, nbytes = run.model.step_work(run.cfg, lives, steps)
    if spec["kind"] == "mfu":
        return 100.0 * flops / steps / dev_s / run.peaks["bf16_flops"]
    least, _bound = least_seconds(flops / steps, nbytes / steps, run.peaks)
    return 100.0 * least / dev_s
