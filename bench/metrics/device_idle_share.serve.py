"""Share of the traced serving window in which the chip ran no
operation, in %."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    return 100.0 * rec["timeline"].idle_share()
