"""Device self time of every op of the step that JAX marked transposed
(the backward pass, with the forward recomputed under remat) per traced
step, mean over the chips, in ms."""
import scopes


def read(rec):
    return scopes.step_ms(rec, pass_="bwd")
