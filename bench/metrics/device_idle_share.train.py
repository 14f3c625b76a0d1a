"""Share of the traced training window in which the busiest chip ran no
operation, in %."""


def read(rec):
    if rec["kind"] != "train":
        return None
    return 100.0 * rec["timeline"].idle_share()
