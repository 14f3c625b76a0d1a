"""Share of the chips' busy time in the traced window spent in ops under
none of the program's layer names (the step's unnamed ops and any other
program's), in %: how much of the step the layer metrics cover."""
import scopes


def read(rec):
    keys = scopes.of_run(rec)
    if keys is None or not scopes.names_layers(keys):
        return None
    tl = rec["timeline"]
    loose = sum(scopes.layer_ns(k, lay) for k in keys.values()
                for lay in (scopes.OTHER, scopes.UNSCOPED))
    return 100.0 * loose / sum(tl.busy_ns(c) for c in tl.chips)
