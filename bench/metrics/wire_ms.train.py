"""Device time in collective operations per traced step, mean over the
chips, in ms.  Nothing to read where no collective ran."""


def read(rec):
    if rec["kind"] != "train" or not rec["window"].traced_steps:
        return None
    tl = rec["timeline"]
    total = [tl.collective_ns(d)[0] for d in tl.chips]
    if not any(total):
        return None
    return sum(total) / len(total) * 1e-6 / rec["window"].traced_steps
