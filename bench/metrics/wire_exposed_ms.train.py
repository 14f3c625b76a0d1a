"""The part of each traced step's collective time during which no other
operation runs on that chip, mean over the chips, in ms.  Nothing to
read where no collective ran."""


def read(rec):
    if rec["kind"] != "train" or not rec["window"].traced_steps:
        return None
    tl = rec["timeline"]
    pairs = [tl.collective_ns(d) for d in tl.chips]
    if not any(t for t, _ in pairs):
        return None
    return sum(e for _, e in pairs) / len(pairs) * 1e-6 \
        / rec["window"].traced_steps
