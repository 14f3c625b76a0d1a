"""Model FLOPs of the tokens prefilled and decoded in the traced window
(a prompt counts when its first token is served) over the chip's busy
time and its bf16 peak, in %: the step's efficiency, not the offered
load."""
import harness


def read(rec):
    if rec["kind"] != "serve" or rec["window"].trace is None:
        return None
    t_a, t_b = rec["window"].trace
    work = sum(f for t, f in rec["window"].steps if t_a <= t <= t_b)
    tl = rec["timeline"]
    busy = max(tl.busy_ns(d) for d in tl.chips) * 1e-9
    if work <= 0 or busy <= 0:
        return None
    peak = harness.load_peaks(rec["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * work / (busy * peak)
