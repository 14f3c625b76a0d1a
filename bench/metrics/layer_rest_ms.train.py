"""Device self time of the ops under the program's ``layers`` scope and
in none within it: the layer scan's own slicing and stacking of weights
and gradients, the carry and the residual adds, attention and MLP left
out; per traced step, mean over the chips, in ms."""
import scopes


def read(rec):
    return scopes.step_ms(rec, "layers")
