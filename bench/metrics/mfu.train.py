"""Model FLOPs of the traced training steps over the traced window,
the chips and the chip's bf16 peak, in %."""
import flops
import harness


def read(rec):
    if rec["kind"] != "train" or not rec["window"].traced_steps:
        return None
    job, tl = rec["job"], rec["timeline"]
    work = flops.train_step_flops(job.conf, job.W, job.B, job.S) \
        * rec["window"].traced_steps
    peak = harness.load_peaks(rec["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * work / (tl.window_s * len(tl.chips) * peak)
