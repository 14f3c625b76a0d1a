"""Device time of a training step by the layers the program names.

The program names each layer of its training step with
`jax.named_scope` (`LAYERS`).  The compiler keeps an op's name stack as
the ``op_name`` metadata of its HLO instruction, ``jit(step)/vmap(
transpose(jvp(layers)))/while/body/.../attn/dot_general``; a fusion
carries its root op's, so a fusion is charged whole to one layer.

On a TPU v5e the trace's "XLA Ops" events carry no ``op_name``: each
is named by its HLO instruction's text, and its stats are only
``device_offset_ps`` and ``device_duration_ps``.  `timeline.extract`
keeps each op's self time under that instruction's name (``fusion.704
bf16[...]``); `split` joins the names against the ``op_name`` metadata
of the compiled step's HLO text (`step_op_names`, `op_names`) and sums
the self time under ``layer/pass``:

- ``layer``: the innermost of `LAYERS` that is a segment of the name
  stack; `OTHER` for an op of the step under none of them; `UNSCOPED`
  for an op the step's HLO does not hold (another program's);
- ``pass``: ``bwd`` where JAX marked the op transposed (the backward
  pass, the forward recomputed under remat included), else ``fwd``.

Self times cover the whole trace, which starts and ends on a ready
state: the traced window's device work.  A program that names none of
its layers (the seed's, a serving cell) reads nothing.

A reader of a part of a layer (a scope the program names inside one,
such as the router of an MoE ``mlp``) asks for the ops whose name stack
holds that segment (`step_ms`'s ``within``); the segment need not be
one of `LAYERS`, so the split by layer stays as it is.
"""
from __future__ import annotations

import re

# the names the program gives its training step's layers: the model
# step's own, and the reducer's (which the HLO lint reads too)
MODEL_LAYERS = ("embed", "attn", "mlp", "layers", "head", "tail")
LAYERS = MODEL_LAYERS + ("wire",)
OTHER, UNSCOPED = "other", "unscoped"
# a name-stack segment inside its transformations: ``vmap(jvp(attn))``
WRAPPED = re.compile(r"(?:[\w.-]+\()*([^()]*?)\)*")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
REF = re.compile(r"%([\w.\-]+)")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# how far a nameless instruction's name is looked for, in instructions
NEAR = 64


def segments(op_name: str) -> list:
    """The segments of a name stack, each out of its transformations."""
    out = []
    for seg in op_name.split("/"):
        m = WRAPPED.fullmatch(seg)
        out.append(m.group(1) if m else seg)
    return out


def scope_of(op_name: str):
    """The innermost of `LAYERS` that is a segment of ``op_name``, or
    None."""
    found = None
    for core in segments(op_name):
        if core in LAYERS:
            found = core
    return found


def layer_key(op_name) -> str:
    """``layer/pass`` of an op with name stack ``op_name`` (None for an
    op the step does not hold)."""
    if op_name is None:
        return f"{UNSCOPED}/fwd"
    layer = scope_of(op_name) or OTHER
    return f"{layer}/{'bwd' if 'transpose(' in op_name else 'fwd'}"


def op_names(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` of every instruction of an HLO
    module's text ('' where none can be found).  An instruction the
    compiler made without a name stack (a layout copy, one named by the
    argument it copies, the halves of an async copy, a multi-output
    fusion whose root is a tuple) takes the name of its fused
    computation's root, else of its nearest user that has one, else of
    its nearest operand, else of the instruction that calls its
    computation (a loop body's ``while``)."""
    comps, comp = {}, None
    for line in hlo_text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = comps.setdefault(m.group(1), [])
            continue
        m = INSTRUCTION.match(line)
        if m and comp is not None:
            meta = OP_NAME.search(line)
            # a name stack has segments; an argument's path has none
            op = meta.group(1) if meta and "/" in meta.group(1) else ""
            comp.append((m.group(1), op,
                         line.startswith("  ROOT"), REF.findall(line)))
    own, args, users, called, root, home, caller = ({} for _ in range(7))
    for cname, instrs in comps.items():
        for name, op, is_root, refs in instrs:
            own[name], home[name] = op, cname
            called[name] = [r for r in refs if r in comps]
            args[name] = [r for r in refs if r not in comps and r != name]
            for a in args[name]:
                users.setdefault(a, []).append(name)
            for c in called[name]:
                caller.setdefault(c, name)
            if is_root:
                root[cname] = name

    def walk(starts, step, check):
        """Breadth first from ``starts`` along ``step``, the first name
        ``check`` finds within `NEAR` instructions."""
        queue, seen = list(starts), set(starts)
        for name in queue:
            if len(seen) > NEAR:
                break
            found = check(name)
            if found:
                return found
            for nxt in step(name):
                if nxt not in seen:
                    seen.add(nxt)
                    queue.append(nxt)
        return ""

    def fused(name):
        return [root[c] for c in called[name] if c in root]

    def named(name):
        """Its own name, else its fused computation's, from the root
        toward the parameters."""
        return own[name] or walk(fused(name), lambda n: fused(n) + args[n],
                                 own.get)

    out = {}

    def resolve(name):
        if name not in out:
            out[name] = (
                named(name)
                or walk(users.get(name, []), lambda n: users.get(n, []),
                        named)
                or walk(args[name], args.get, named)
                or (resolve(caller[home[name]]) if home[name] in caller
                    else ""))
        return out[name]

    for name in own:
        resolve(name)
    return out


def split(plain: dict, names: dict, within=None) -> dict:
    """Per chip of a plain trace, self ns under each ``layer/pass``;
    ``names`` as `op_names` gives them for the step.  With ``within``,
    of the ops whose name stack holds that segment alone."""
    out = {}
    for chip, dev in plain["devices"].items():
        keys: dict = {}
        for op, ns in dev["self"].items():
            op_name = names.get(op.split(" ", 1)[0])
            if within is not None and (
                    op_name is None or within not in segments(op_name)):
                continue
            key = layer_key(op_name)
            keys[key] = keys.get(key, 0) + ns
        out[chip] = keys
    return out


def layer_ns(keys: dict, layer=None, pass_=None) -> int:
    """Self ns of one chip's `split` under ``layer`` (any of the step's
    where None) and ``pass_`` (both where None)."""
    total = 0
    for key, ns in keys.items():
        lay, ps = key.split("/")
        if (lay == layer or (layer is None and lay != UNSCOPED)) \
                and pass_ in (None, ps):
            total += ns
    return total


def names_layers(keys_by_chip: dict) -> bool:
    """Whether the step names its model layers at all."""
    return any(layer_ns(k, lay) for k in keys_by_chip.values()
               for lay in MODEL_LAYERS)


def step_op_names(job) -> dict:
    """`op_names` of the training step the window ran: ``job.step_fn``
    lowered again for the state and batch it ran on; the compile comes
    from the cache."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    import train

    def placed(tree, shardings):
        # one chip: the arrays the step ran on were on the job's device
        if shardings is None:
            shardings = jax.tree.map(
                lambda _: SingleDeviceSharding(job.devices[0]), tree)
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    state = jax.eval_shape(lambda k: job.alg.init(job.model.init(k)),
                           jax.random.PRNGKey(0))
    batch = jax.eval_shape(lambda: jax.tree.map(
        jnp.asarray, train.make_batch(0, 0, job)))
    lowered = job.step_fn.lower(placed(state, job.st_sh),
                                placed(batch, job.b_sh))
    return op_names(lowered.compile().as_text())


def of_run(rec, within=None):
    """The traced training run's `split` (``within`` a segment, where
    given), the whole one computed once; None for any other run."""
    if rec["kind"] != "train" or not rec["window"].traced_steps \
            or "timeline" not in rec:
        return None
    if within is not None:
        return split(rec["timeline"].plain, _names(rec), within)
    if "layer_split" not in rec:
        rec["layer_split"] = split(rec["timeline"].plain, _names(rec))
    return rec["layer_split"]


def _names(rec) -> dict:
    """`step_op_names` of the run's step, computed once."""
    if "op_names" not in rec:
        rec["op_names"] = step_op_names(rec["job"])
    return rec["op_names"]


def step_ms(rec, layer=None, pass_=None, within=None):
    """Self ms per traced step under ``layer``/``pass_`` (of the ops
    whose name stack holds the segment ``within``, where given), mean
    over the chips; None where the step names no model layer or nothing
    ran there."""
    keys = of_run(rec)
    if keys is None or not names_layers(keys):
        return None
    if within is not None:
        keys = of_run(rec, within)
    ns = sum(layer_ns(k, layer, pass_) for k in keys.values()) / len(keys)
    if ns <= 0:
        return None
    return ns * 1e-6 / rec["window"].traced_steps
