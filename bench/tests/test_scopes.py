"""Device time by the program's layer names: name stacks to
``layer/pass``, op names found for the compiler's nameless instructions,
the split of recorded traces, and the readers on a step compiled on the
CPU."""
import time
from pathlib import Path

import jax
import pytest

import harness
import scopes
import timeline
import train
from small import train_traffic

DATA = Path(__file__).parent / "data"
PLAIN = sorted(p for p in DATA.glob("timeline-*.json.gz")
               if ".scoped." not in p.name)
SCOPED = sorted(DATA.glob("timeline-*.scoped.json.gz"))
READERS = ("attn_ms.train", "mlp_ms.train", "head_ms.train",
           "layer_rest_ms.train", "tail_ms.train", "backward_ms.train",
           "unscoped_share.train")


@pytest.mark.parametrize("op_name,key", [
    ("jit(step)/vmap(jvp(layers))/while/body/closed_call/attn/dot_general",
     "attn/fwd"),
    # the backward pass, and the forward recomputed inside it
    ("jit(step)/vmap(transpose(jvp(layers)))/while/body/closed_call/"
     "checkpoint/rematted_computation/mlp/jit(silu)/mul", "mlp/bwd"),
    ("jit(step)/vmap(transpose(jvp(head)))/while/body/dot_general",
     "head/bwd"),
    # the scan's own ops keep the outer name
    ("jit(step)/vmap(transpose(jvp(layers)))/while/body/dynamic_slice",
     "layers/bwd"),
    ("jit(step)/tail/wire/convert_element_type", "wire/fwd"),
    ("jit(step)/vmap(jvp(embed))/jit(_take)/gather", "embed/fwd"),
    # names only as whole segments
    ("jit(step)/vmap(jvp())/convert_element_type", "other/fwd"),
    ("jit(attention)/mlp_out/add", "other/fwd"),
    ("", "other/fwd"),
    # an op the step does not hold
    (None, "unscoped/fwd"),
])
def test_layer_of_a_name_stack(op_name, key):
    assert scopes.layer_key(op_name) == key


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> (f32[8], f32[8]) {
  %param_0 = f32[8]{0} parameter(0)
  %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(step)/tail/add"}
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%add.1, %add.1)
}

%body (carry: (f32[8])) -> (f32[8]) {
  %carry = (f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%carry), index=0
  %copy.3 = f32[8]{0} copy(%get-tuple-element.1)
  ROOT %tuple.2 = (f32[8]{0}) tuple(%copy.3)
}

%cond (carry.1: (f32[8])) -> pred[] {
  %carry.1 = (f32[8]{0}) parameter(0)
  ROOT %constant.1 = pred[] constant(false)
}

ENTRY %main (p.0: f32[8], p.1: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0), metadata={op_name="state.params[\\'w\\']"}
  %p.1 = f32[8]{0} parameter(1)
  %copy.2 = f32[8]{1,0} copy(%p.0), metadata={op_name="state.params[\\'w\\']"}
  %fusion.3 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/vmap(jvp(layers))/attn/mul"}
  %copy-start.4 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%fusion.3)
  %copy-done.5 = f32[8]{0} copy-done(%copy-start.4)
  %multi.6 = (f32[8]{0}, f32[8]{0}) fusion(%p.1), kind=kLoop, calls=%fused_computation
  %lonely.7 = f32[8]{0} negate(%p.1)
  %tuple.10 = (f32[8]{0}) tuple(%p.1)
  %while.9 = (f32[8]{0}) while(%tuple.10), condition=%cond, body=%body, metadata={op_name="jit(step)/vmap(transpose(jvp(layers)))/while/body/attn/while"}
  ROOT %tuple.8 = (f32[8]{0}, f32[8]{0}) tuple(%copy-done.5, %multi.6)
}
"""


def test_nameless_instructions_find_a_name():
    names = scopes.op_names(HLO)
    # a multi-output fusion: its root is a tuple, the tuple's element
    assert names["multi.6"] == "jit(step)/tail/add"
    # the halves of an async copy: the copied op's
    assert scopes.scope_of(names["copy-done.5"]) == "attn"
    assert scopes.scope_of(names["copy-start.4"]) == "attn"
    # a copy named by the argument it copies: its user's
    assert scopes.scope_of(names["copy.2"]) == "attn"
    # a loop carry's copy: the loop's
    assert scopes.layer_key(names["copy.3"]) == "attn/bwd"
    assert names["lonely.7"] == ""
    assert scopes.layer_key(names["lonely.7"]) == "other/fwd"


def test_split_and_sums():
    plain = {"devices": {"0": {"self": {
        "fusion.3 f32[8]": 50, "copy-done.5 f32[8]": 10,
        "multi.6 (f32[8], f32[8])": 30, "lonely.7 f32[8]": 5,
        "copy.9 f32[8]": 5}}}, "host": []}
    keys = scopes.split(plain, scopes.op_names(HLO))["0"]
    assert keys == {"attn/fwd": 60, "tail/fwd": 30, "other/fwd": 5,
                    "unscoped/fwd": 5}
    assert scopes.layer_ns(keys) == 95
    assert scopes.layer_ns(keys, "attn") == 60
    assert scopes.layer_ns(keys, pass_="bwd") == 0
    assert scopes.names_layers({"0": keys})


@pytest.mark.parametrize("path", PLAIN, ids=lambda p: p.name)
def test_plain_traces_without_names_still_load(path):
    """A trace of a step that names no layer (the seed's) still loads,
    and its layer metrics read nothing."""
    plain = timeline.load(path)
    tl = timeline.Timeline(plain)
    rec = {"kind": "train", "timeline": tl,
           "window": train.Window(traced_steps=3),
           "layer_split": scopes.split(plain, {})}
    assert not scopes.names_layers(rec["layer_split"])
    for name in READERS:
        assert harness.metric_reader(name)(rec) is None


def test_a_scoped_trace_is_recorded():
    assert SCOPED


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.name)
def test_recorded_scoped_trace(path):
    """A trace recorded on the chip with its step's op names: the layers
    and the loose ops add up to the busy time, few ops are loose, and
    attention leads."""
    plain = timeline.load(path)
    tl = timeline.Timeline(plain)
    keys = scopes.split(plain, plain["op_names"])
    for c in tl.chips:
        total = sum(keys[c].values())
        assert total == pytest.approx(tl.busy_ns(c), rel=0.02)
        loose = scopes.layer_ns(keys[c], scopes.OTHER) \
            + scopes.layer_ns(keys[c], scopes.UNSCOPED)
        assert loose < 0.05 * total
        by_layer = {lay: scopes.layer_ns(keys[c], lay)
                    for lay in scopes.LAYERS}
        assert max(by_layer, key=by_layer.get) == "attn"


def test_readers_on_a_step_compiled_here(tiny):
    """The op names of a tiny two-worker step, compiled on the CPU, and
    the readers on a made-up trace of one op of each kind."""
    job = train.build(tiny["tiny-qwen3"], train_traffic(workers=2),
                      jax.devices()[:1])
    train.checked_steps(job, 5, harness.seed_key(5))
    names = scopes.step_op_names(job)
    by_key = {}
    for instr, op in names.items():
        by_key.setdefault(scopes.layer_key(op), instr)
    assert {k.split("/")[0] for k in by_key} >= set(scopes.LAYERS)
    ops = {f"{instr} f32[8]": 1_000_000 for instr in by_key.values()}
    ops["not-in-the-step.1 f32[8]"] = 1_000_000
    plain = {"devices": {"0": {"top": [["x", 0, len(ops) * 1_000_000]],
                               "self": ops}},
             "host": [["bench.batch", 0, 1], ["bench.fetch", 1,
                                               len(ops) * 1_000_000]]}
    rec = {"kind": "train", "job": job, "timeline": timeline.Timeline(plain),
           "window": train.Window(traced_steps=2)}
    t = time.perf_counter()
    values = {n: harness.metric_reader(n)(rec) for n in READERS}
    assert time.perf_counter() - t < 60
    assert values["attn_ms.train"] == pytest.approx(
        sum(k.startswith("attn/") for k in by_key) * 0.5)
    assert values["backward_ms.train"] == pytest.approx(
        sum(k.endswith("/bwd") and not k.startswith("unscoped")
            for k in by_key) * 0.5)
    loose = sum(k.split("/")[0] in ("other", "unscoped") for k in by_key)
    assert values["unscoped_share.train"] == pytest.approx(
        100 * (loose + 1) / len(ops))
