"""The control: the reference computed in fp8, put in the program's
place, comes out as not correct where sound runs of the program pass.
On the CPU at small sizes; on the chip, `calibrate.py` reads the same
numbers at the cells' own sizes."""
import jax

import serve
import train
from small import SERVE_LIMITS, TRAIN_LIMITS, serve_traffic, train_traffic

SEEDS = (7, 2**40 + 3)


def fails(nums, limits):
    return any(nums[k] > limits[k] for k in limits)


def test_train_control_and_faults_fail_where_sound_runs_pass(tiny):
    for seed in SEEDS:
        row = train.calibrate(tiny["tiny-stablelm"],
                              train_traffic(2, ("--buckets", "2")),
                              jax.devices()[:1], seed)
        assert not fails(row["sound"], TRAIN_LIMITS), row["sound"]
        assert fails(row["control"], TRAIN_LIMITS), row["control"]
        assert fails(row["half_batch"], TRAIN_LIMITS), row["half_batch"]
        assert fails(row["no_exchange"], TRAIN_LIMITS), row["no_exchange"]


def test_serve_control_fails_where_sound_runs_pass(tiny):
    rows = serve.calibrate(tiny["tiny-qwen3"], serve_traffic(),
                           jax.devices()[:1], list(SEEDS), 4.0)
    for row in rows:
        assert row["sound"] <= SERVE_LIMITS["served_gap"] < row["control"]
        assert row["tokens"] >= 20
