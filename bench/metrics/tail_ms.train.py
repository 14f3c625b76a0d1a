"""Device self time of the ops under the program's ``tail`` scope (the
DC-S3GD update over every parameter: the distance to the average, the
compensator, the local optimizer, Eq. 12 and the next wire state) per
traced step, mean over the chips, in ms."""
import scopes


def read(rec):
    return scopes.step_ms(rec, "tail")
