"""Device self time of the ops under the program's ``head`` scope (the
final norm, the unembedding and the cross-entropy over the vocabulary,
forward and backward) per traced step, mean over the chips, in ms."""
import scopes


def read(rec):
    return scopes.step_ms(rec, "head")
