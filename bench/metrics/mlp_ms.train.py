"""Device self time of the ops under the program's ``mlp`` scope (the
pre-norm and the feed-forward or experts: forward, backward and the
recompute) per traced step, mean over the chips, in ms."""
import scopes


def read(rec):
    return scopes.step_ms(rec, "mlp")
