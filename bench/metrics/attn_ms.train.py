"""Device self time of the ops under the program's ``attn`` scope (the
pre-norm and attention: forward, backward and the recompute) per traced
step, mean over the chips, in ms."""
import scopes


def read(rec):
    return scopes.step_ms(rec, "attn")
