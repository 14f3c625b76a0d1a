"""Small traffic and limits for driving whole runs on the CPU."""
import copy

import harness

# set from CPU readings of the small configurations: sound runs read
# loss <= 4.5e-4, grad <= 3e-3, change <= 3e-3, served <= 0.06; the fp8
# control reads loss >= 2.3e-3, grad >= 0.024, change >= 0.017,
# served >= 0.5
TRAIN_LIMITS = {"loss_gap": 1.5e-3, "grad_gap": 0.01, "change_gap": 0.01}
SERVE_LIMITS = {"served_gap": 0.2}


def train_traffic(workers=1, extra=()):
    tr = copy.deepcopy(harness.load_traffic("train-w1-2x2048"))
    flags = tr["trainer_flags"]
    i = flags.index("--workers")
    tr["trainer_flags"] = flags[:i] + [
        "--workers", str(workers), "--batch-per-worker", "2",
        "--seq", "32"] + list(extra)
    tr["trace_steps"] = 2
    return tr


def serve_traffic(name="chat-overload"):
    """The mix ``name`` at a small size: past the knee by default (a
    backlog of 48 for 4 slots and 20 requests a second, no drain),
    below it for ``chat`` (3 a second, drained)."""
    tr = copy.deepcopy(harness.load_traffic(name))
    tr["server"].update(slots=4, pages=4 * 10 + 1, max_len=160,
                        prefill_chunk=32)
    tr.update(prompt_len={"median": 40, "sigma": 0.8, "min": 4, "max": 100},
              output_len={"median": 12, "sigma": 0.8, "min": 2, "max": 50},
              check_tokens_min=20)
    if tr.get("backlog"):
        tr.update(backlog=48,
                  arrivals={"process": "poisson", "rate_per_s": 20.0})
    else:
        tr.update(drain_limit_s=30,
                  arrivals={"process": "poisson", "rate_per_s": 3.0})
    return tr
