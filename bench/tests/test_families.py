"""The family seam: a configuration file names its family, and the
harness takes all that it knows of the architecture from
``bench/families/<family>.py``, so a new architecture adds files and
edits none."""
import importlib
import json
import sys
import types

import jax
import numpy as np
import pytest

import flops
import harness
import model as bm
import serve
import train
from small import train_traffic

DECODER = importlib.import_module("families.decoder")
INTERFACE = ("widths", "reference_config", "fan_in", "seq_loss", "hidden",
             "unembed", "matmul_params", "pair_flops")
SEED = 2**40 + 3

# The reference's readings of the three checked steps at the tiny sizes,
# two workers, seed SEED, as `train.reference_readings` gave them on the
# CPU before the decoder moved out of `reference.py` into its family:
# float32 values, to be met bit for bit.
PARENT = {
    "tiny-qwen3": {
        "losses": [6.752079963684082, 6.639791965484619, 6.715627670288086],
        "grad_norms": [
            [14.4686861038208, 14.178997039794922],
            [0.17300714552402496, 0.20013469457626343],
            [0.19330237805843353, 0.13294146955013275],
            [0.19512535631656647, 0.12797953188419342],
            [1.2212330102920532, 1.1212854385375977],
            [1.7856210470199585, 1.8217438459396362],
            [1.2461225986480713, 1.2111873626708984],
            [1.7780017852783203, 1.642857313156128],
            [0.29618334770202637, 0.26774463057518005],
            [0.17993979156017303, 0.18990178406238556],
            [1.5641018152236938, 1.4438066482543945],
            [1.1510345935821533, 1.1128177642822266],
            [1.0734182596206665, 1.096201777458191],
            [0.9992498159408569, 1.0143249034881592],
        ],
        "change_norms": [
            [1.7251412868499756, 1.716080665588379],
            [0.029353836551308632, 0.0339638888835907],
            [0.024979382753372192, 0.015359611250460148],
            [0.023969996720552444, 0.016086310148239136],
            [0.14645496010780334, 0.1426181197166443],
            [0.21724790334701538, 0.20619778335094452],
            [0.1414850652217865, 0.14361655712127686],
            [0.21661648154258728, 0.21376849710941315],
            [0.03928743675351143, 0.034701697528362274],
            [0.023485390469431877, 0.022938093170523643],
            [0.17866019904613495, 0.17272870242595673],
            [0.13258421421051025, 0.13208778202533722],
            [0.1264919936656952, 0.13075582683086395],
            [0.11398965865373611, 0.11530254036188126],
        ],
    },
    "tiny-stablelm": {
        "losses": [6.782834053039551, 6.7824578285217285, 6.656198978424072],
        "grad_norms": [
            [15.56541919708252, 16.897836685180664],
            [0.13233131170272827, 0.14437763392925262],
            [0.17159870266914368, 0.18164242804050446],
            [1.47170889377594, 1.4890775680541992],
            [2.1298067569732666, 2.3156731128692627],
            [1.4695329666137695, 1.4485576152801514],
            [2.2258102893829346, 2.3678667545318604],
            [0.6797602772712708, 0.674737274646759],
            [0.3185284733772278, 0.38490578532218933],
            [0.22481802105903625, 0.2152893841266632],
            [0.22507837414741516, 0.23610009253025055],
            [1.4234113693237305, 1.456273078918457],
            [1.3204796314239502, 1.372409462928772],
            [1.3134174346923828, 1.3463889360427856],
            [1.1137425899505615, 1.137447476387024],
        ],
        "change_norms": [
            [1.618232250213623, 1.724283218383789],
            [0.017222587019205093, 0.017088714987039566],
            [0.02972973696887493, 0.0293840691447258],
            [0.15142682194709778, 0.1589294821023941],
            [0.21945621073246002, 0.23021700978279114],
            [0.15269175171852112, 0.16130003333091736],
            [0.22762919962406158, 0.22831188142299652],
            [0.05981067568063736, 0.07472562044858932],
            [0.03538813441991806, 0.04249295964837074],
            [0.024007383733987808, 0.024152815341949463],
            [0.021595994010567665, 0.02426738105714321],
            [0.1517993062734604, 0.15461957454681396],
            [0.13687045872211456, 0.13913078606128693],
            [0.13382549583911896, 0.14414700865745544],
            [0.1274876594543457, 0.1290302723646164],
        ],
    },
}


@pytest.mark.parametrize("name,params", [("qwen3-0.6b", 595_984_384),
                                         ("stablelm-3b-6l", 604_569_600)])
def test_configurations_are_decoders(name, params):
    b = harness.load_benchmark()
    conf = harness.load_config({c["name"]: c for c in b["configs"]}[name])
    assert harness.family(conf) is DECODER
    assert DECODER.matmul_params(conf) == params


def test_tiny_configurations_are_decoders(tiny):
    assert all(harness.family(c) is DECODER for c in tiny.values())


def test_an_unknown_family_names_the_file_to_add(tiny, tmp_path):
    conf = dict(tiny["tiny-qwen3"], family="nope")
    (tmp_path / "c.json").write_text(json.dumps(conf))
    with pytest.raises(ValueError, match="add bench/families/nope.py"):
        harness.load_config({"file": "c.json"}, root=tmp_path)
    # no default family
    del conf["family"]
    with pytest.raises(KeyError):
        harness.family(conf)


def test_a_new_family_needs_no_edit(tiny, monkeypatch):
    """A family module in `sys.modules` and a configuration naming it
    route the width check, the weights, the reference's loss, the FLOP
    count and serving's check."""
    calls = set()

    def recording(name, fn):
        def call(*args, **kwargs):
            calls.add(name)
            return fn(*args, **kwargs)
        return call

    stub = types.ModuleType("families.stub")
    for name in INTERFACE:
        setattr(stub, name, recording(name, getattr(DECODER, name)))
    monkeypatch.setitem(sys.modules, "families.stub", stub)
    conf = dict(tiny["tiny-qwen3"], family="stub")

    job = train.build(conf, train_traffic(), jax.devices()[:1])
    assert job.family is stub and calls == {"widths"}
    key = harness.seed_key(SEED)
    train.checked_steps(job, SEED, key)
    assert "fan_in" in calls
    train.reference_readings(job, SEED, key)
    assert {"reference_config", "seq_loss"} <= calls
    assert flops.forward_flops(conf, 10, 20) == \
        2.0 * DECODER.matmul_params(conf) * 10 + DECODER.pair_flops(conf) * 20
    params = jax.jit(lambda k: bm.make_weights(stub, job.template, k))(key)
    served = np.zeros(4, np.int32)
    serve._gap_fn(conf, False)(params, np.zeros(16, np.int32),
                               np.arange(4, dtype=np.int32), served)
    assert calls == set(INTERFACE)


@pytest.mark.parametrize("name", ["tiny-qwen3", "tiny-stablelm"])
def test_the_reference_computes_what_it_did(tiny, name):
    job = train.build(tiny[name], train_traffic(workers=2),
                      jax.devices()[:1])
    ref = train.reference_readings(job, SEED, harness.seed_key(SEED))
    want = PARENT[name]
    assert ref.losses == want["losses"]
    np.testing.assert_array_equal(ref.grad_norms,
                                  np.float32(want["grad_norms"]))
    np.testing.assert_array_equal(ref.change_norms,
                                  np.float32(want["change_norms"]))
