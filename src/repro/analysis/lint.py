"""Pass-based static analyzer over the ACTUAL lowered train programs.

`repro.analysis.lint` audits what the compiler will run, not what the
source says: each grid point (algo x reducer x buckets x overlap) is
lowered through the Engine (`Engine.lower_train_step`) and a set of
passes checks the invariants DC-S3GD's correctness story rests on —
donation coverage, no host syncs in the step, no steady-state retraces,
no dtype drift beyond the declared ``comm_dtype`` wire casts, pipeline
fencing, and the wire-bytes accounting cross-check.  Layer 2
(`repro.analysis.astlint`) lints the source tree for the repo rules the
ROADMAP states.  Findings serialize through `repro.analysis.report`
(``repro.lint/v1``) and gate CI against the committed zero-findings
baseline (``LINT_BASELINE.json``).

CLI (also installed as the ``repro-lint`` console script)::

    python -m repro.analysis.lint                  # full grid + AST lint
    python -m repro.analysis.lint --select topk    # grid-point substring
    python -m repro.analysis.lint --json report.json --baseline LINT_BASELINE.json
    python -m repro.analysis.lint --list           # show the grid

Exit status 1 iff any non-baseline finding was produced — see
``docs/analysis.md`` for the pass catalog and baseline workflow.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.hlo import count_ops
from repro.analysis.report import (Finding, findings_report, load_baseline,
                                   new_findings, render_findings)
from repro.core import quant as Q
from repro.core import registry
from repro.core.types import DCS3GDConfig
from repro.launch.engine import Engine

PyTree = Any

# ---------------------------------------------------------------------------
# the grid
# ---------------------------------------------------------------------------

ALGOS = ("dc_s3gd", "ssgd")
DENSE_REDUCERS = ("mean_allreduce", "gossip", "hierarchical")
COMPRESSED_REDUCERS = ("topk", "topk_exact", "randk", "powersgd")
BUCKET_SETTINGS = (0, 4)
N_WORKERS = 2


@dataclass(frozen=True)
class GridPoint:
    algo: str
    reducer: str
    buckets: int
    overlap: bool
    wire: str = "bfloat16"   # comm_dtype of the audited program

    @property
    def name(self) -> str:
        base = (f"{self.algo}/{self.reducer}/b{self.buckets}/"
                f"{'ov' if self.overlap else 'in'}")
        # baseline names stay stable: only non-default wires get a suffix
        return base if self.wire == "bfloat16" else f"{base}/{self.wire}"


def iter_grid() -> Iterator[GridPoint]:
    """Every *valid* grid point: compressed reducers need the bucketed
    wire; the overlap pipeline needs buckets > 0 and a stale-family
    algorithm (ssgd's blocking reduce has nothing to overlap — the
    constructor raises)."""
    for algo in ALGOS:
        for reducer in DENSE_REDUCERS + COMPRESSED_REDUCERS:
            for buckets in BUCKET_SETTINGS:
                if reducer in COMPRESSED_REDUCERS and not buckets:
                    continue
                for overlap in (False, True):
                    # grid enumeration, not dispatch: ssgd's constructor
                    # itself rejects overlap=True
                    if overlap and (algo == "ssgd"  # lint: allow(algo-branch)
                                    or not buckets):
                        continue
                    yield GridPoint(algo, reducer, buckets, overlap)
    # one quantized-wire point so the wire-accounting gate covers the
    # int8 byte model (quantize cast census + scale bytes)
    yield GridPoint("dc_s3gd", "topk", 4, False, wire="int8")


# ---------------------------------------------------------------------------
# program under audit
# ---------------------------------------------------------------------------


class _ToyModel:
    """Minimal Engine model shim: a many-leaf quadratic so bucketing,
    donation, and the wire are all exercised without a transformer
    compile.  f32 activations — any float down-cast in the lowered step
    is either the declared comm_dtype wire cast or a finding."""

    cfg = None
    N_LEAVES = 6
    DIM = 16

    def init(self, key) -> PyTree:
        ks = jax.random.split(key, self.N_LEAVES)
        return {f"w{i}": jax.random.normal(ks[i], (self.DIM, self.DIM),
                                           jnp.float32) * 0.02
                for i in range(self.N_LEAVES)}

    def loss(self, params, batch):
        acc = 0.0
        for v in params.values():
            acc = acc + jnp.mean((batch["x"] @ v) ** 2)
        return acc


def _toy_batch(n_workers: int) -> dict:
    return {"x": jnp.ones((n_workers, 2, _ToyModel.DIM), jnp.float32)}


def _transformer_setup():
    """The reduced CI transformer (same model `benchmarks/step_time.py`
    times) — the ``--model transformer`` deep audit."""
    from repro.configs import get_config, reduced
    from repro.data import SyntheticLMDataset, worker_batches
    from repro.models.transformer import Model

    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg, remat=False, scan_chunk=16, loss_chunk=64)
    data = SyntheticLMDataset(cfg.vocab_size, 32, seed=0)
    return model, worker_batches(data, 0, N_WORKERS, 2)


# MLIR float element types <-> numpy names and wire byte widths
_MLIR_FLOATS = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2}
# quantized wire storage types (1 B payload each) — the census widths
# the wire-accounting pass uses cover floats AND quantized dsts
_MLIR_QUANT = {"i8": 1, "f8E4M3FN": 1, "f8E5M2": 1}
_MLIR_WIRE = {**_MLIR_FLOATS, **_MLIR_QUANT}
_NP_TO_MLIR = {"float64": "f64", "float32": "f32", "float16": "f16",
               "bfloat16": "bf16", "int8": "i8",
               "float8_e4m3fn": "f8E4M3FN", "float8_e5m2": "f8E5M2"}


class Program:
    """One grid point's lowered step plus everything the passes need.

    Lowering is lazy and cached; the debug-info ASM (per-op ``loc``
    scopes — how comm_dtype casts are attributed to the ``wire`` named
    scope) is a second lazy view of the same ``Lowered``.
    """

    def __init__(self, point: GridPoint, *, model: str = "toy"):
        self.point = point
        self.name = point.name
        self.model_kind = model
        cfg = DCS3GDConfig(comm_dtype=point.wire, learning_rate=0.05,
                           momentum=0.9, lambda0=0.2, warmup_steps=1,
                           total_steps=4)
        self.cfg = cfg
        self.alg = registry.make(point.algo, cfg, n_workers=N_WORKERS,
                                 reducer=point.reducer,
                                 buckets=point.buckets,
                                 overlap=point.overlap)
        if model == "toy":
            self.model = _ToyModel()
            self.batch = _toy_batch(N_WORKERS)
        else:
            self.model, self.batch = _transformer_setup()
        self.engine = Engine(self.model, self.alg)
        self.state = self.engine.init_state(jax.random.PRNGKey(0))
        self.n_workers = N_WORKERS
        self.comm_mlir = _NP_TO_MLIR[
            str(jnp.dtype(Q.canonical(cfg.comm_dtype)))]
        self._lowered = None
        self._stablehlo: Optional[str] = None
        self._debug: Optional[str] = None

    # -- lazy lowered views -------------------------------------------------

    @property
    def lowered(self):
        if self._lowered is None:
            self._lowered = self.engine.lower_train_step(self.state,
                                                         self.batch)
        return self._lowered

    @property
    def stablehlo(self) -> str:
        if self._stablehlo is None:
            self._stablehlo = self.lowered.as_text()
        return self._stablehlo

    @property
    def stablehlo_debug(self) -> str:
        """The same module with per-op ``loc(#locN)`` references and the
        location table (named-scope strings) — ``Lowered.as_text`` drops
        them, the MLIR printer keeps them."""
        if self._debug is None:
            self._debug = (self.lowered
                           .compiler_ir(dialect="stablehlo")
                           .operation.get_asm(enable_debug_info=True))
        return self._debug

    # -- shapes the passes cross-check against ------------------------------

    @property
    def n_state_leaves(self) -> int:
        return len(jax.tree.leaves(self.state))

    @property
    def wire_sizes(self) -> List[int]:
        """Per-worker element counts the reducer moves: padded
        `BucketPlan` sizes when bucketed, canonical leaf sizes per-leaf
        (same convention as the bench's wire column)."""
        if getattr(self.alg, "buckets", 0):
            return [int(n) for n in
                    self.alg._plan(self.state.params).bucket_sizes]
        # layout fact, not dispatch: dc_s3gd params are (W, ...)
        stacked = self.point.algo != "ssgd"  # lint: allow(algo-branch)
        return [int(x.size // (x.shape[0] if stacked else 1))
                for x in jax.tree.leaves(self.state.params)]

    def batch_fn(self, it: int) -> PyTree:
        """The per-iteration batch the retrace audit drives the fit loop
        with — constant shapes (a steady-state loop) unless a fixture
        overrides it."""
        return self.batch

    def inline_sibling(self) -> "Program":
        assert self.point.overlap, self.name
        return Program(GridPoint(self.point.algo, self.point.reducer,
                                 self.point.buckets, False,
                                 wire=self.point.wire),
                       model=self.model_kind)


# ---------------------------------------------------------------------------
# stablehlo parsing helpers shared by the passes
# ---------------------------------------------------------------------------


def _main_signature(txt: str) -> str:
    """The argument list of ``func.func public @main(...)`` (paren
    balanced — nested tuple/attribute parens included)."""
    i = txt.index("@main(")
    depth = 0
    for j in range(i + len("@main"), len(txt)):
        ch = txt[j]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return txt[i:j + 1]
    return txt[i:]


_CONVERT_RE = re.compile(
    r"stablehlo\.convert\s+%[\w#]+\s*:\s*\(tensor<([^>]*)>\)\s*->\s*"
    r"tensor<([^>]*)>\s*loc\((#loc\d+)\)")
_LOC_RE = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)


def _tensor_spec(spec: str) -> Tuple[Optional[str], Optional[List[int]]]:
    """``"2x32768xbf16"`` -> ("bf16", [2, 32768]); scalars have no dims."""
    parts = spec.split("x")
    dims = []
    for p in parts[:-1]:
        try:
            dims.append(int(p))
        except ValueError:
            return None, None  # dynamic / non-ranked: not our programs
    return parts[-1], dims


@dataclass(frozen=True)
class Convert:
    src: str           # MLIR element type, e.g. "f32"
    dst: str
    elements: int      # product of result dims
    scope: str         # resolved named-scope string ("" if none)


def scoped_converts(debug_asm: str) -> List[Convert]:
    """Every ``stablehlo.convert`` with its result shape and the resolved
    named-scope string of its location (one entry per source-level
    convert — the debug ASM is pre-fusion)."""
    locs = dict(_LOC_RE.findall(debug_asm))
    out: List[Convert] = []
    for src_spec, dst_spec, ref in _CONVERT_RE.findall(debug_asm):
        s_dt, _ = _tensor_spec(src_spec)
        d_dt, d_dims = _tensor_spec(dst_spec)
        if s_dt is None or d_dt is None:
            continue
        n = 1
        for d in d_dims:
            n *= d
        out.append(Convert(src=s_dt, dst=d_dt, elements=n,
                           scope=locs.get(ref, "")))
    return out


def _in_wire_scope(scope: str) -> bool:
    return "/wire/" in scope or scope.endswith("/wire")


# ---------------------------------------------------------------------------
# layer-1 passes
# ---------------------------------------------------------------------------


class DonationPass:
    """Input-output aliasing must cover every TrainState leaf: a donated
    jitted step marks each state argument with ``tf.aliasing_output`` in
    the lowered main signature.  A refactor that silently drops donation
    (a new non-donatable leaf, a changed argument order) doubles peak
    state memory — invisible to every numeric test."""

    name = "donation"

    def run(self, prog: Program) -> List[Finding]:
        sig = _main_signature(prog.stablehlo)
        chunks = sig.split("%arg")[1:]
        aliased = sum("tf.aliasing_output" in c for c in chunks)
        expected = prog.n_state_leaves
        if aliased < expected:
            return [Finding(
                pass_name=self.name, severity="error", program=prog.name,
                op="tf.aliasing_output",
                message=f"only {aliased}/{expected} TrainState leaves are "
                        f"donated (input-output aliased) — donation was "
                        f"dropped for {expected - aliased} buffer(s)")]
        return []


class HostSyncPass:
    """No host transfers inside the jitted step: a python callback /
    infeed / outfeed in the lowered program forces a device->host round
    trip every step, serializing the dispatch queue the overlap design
    depends on."""

    name = "host-sync"

    PATTERNS = ("python_cpu_callback", "python_gpu_callback",
                "stablehlo.infeed", "stablehlo.outfeed",
                "stablehlo.send", "stablehlo.recv")

    def run(self, prog: Program) -> List[Finding]:
        out = []
        for pat in self.PATTERNS:
            n = prog.stablehlo.count(pat)
            if n:
                out.append(Finding(
                    pass_name=self.name, severity="error",
                    program=prog.name, op=pat,
                    message=f"{n} host-transfer op(s) ({pat}) inside the "
                            f"jitted train step — every step pays a "
                            f"device->host round trip"))
        return out


class RetracePass:
    """A steady-state ``Engine.fit`` loop must trace its step exactly
    once (the PR-5 ``Engine.generate`` bug class: a jit rebuilt per call
    recompiles every iteration).  Executes a short constant-shape loop
    and reads the Engine's jit cache-miss counters
    (`Engine.retrace_stats`).  Restricted to the cheap dense points —
    the counter wrapper is entry-point level, not per-reducer."""

    name = "recompile"
    STEPS = 3

    def applies(self, point: GridPoint) -> bool:
        return point.reducer == "mean_allreduce"

    def run(self, prog: Program) -> List[Finding]:
        if not self.applies(prog.point):
            return []
        state = prog.alg.init(prog.model.init(jax.random.PRNGKey(1)))
        prog.engine.fit(state, prog.batch_fn, steps=self.STEPS,
                        log_every=100, verbose=False)
        stats = prog.engine.retrace_stats()
        out = []
        if stats["fit_cache_size"] != 1:
            out.append(Finding(
                pass_name=self.name, severity="error", program=prog.name,
                op="jit-cache",
                message=f"steady-state fit loop traced its step "
                        f"{stats['fit_cache_size']} times over "
                        f"{self.STEPS} constant-shape steps (expected "
                        f"exactly 1)"))
        if stats["fit_rejits"]:
            out.append(Finding(
                pass_name=self.name, severity="error", program=prog.name,
                op="rejit",
                message=f"fit loop re-jitted {stats['fit_rejits']} "
                        f"time(s) without an elastic transition"))
        return out


class DtypeDriftPass:
    """Two prongs.  Structural: the step's output TrainState leaf dtypes
    must equal the input's (a reducer/optimizer that silently adopts a
    narrower dtype corrupts params/opt/``delta_prev``/EF-residual
    carries cumulatively).  Cast census: every float down-cast in the
    lowered body must be either to f32 (the compute dtype) or the
    declared ``comm_dtype`` — and comm-dtype casts must sit under the
    ``wire`` named scope, where the reducers put the simulated wire."""

    name = "dtype-drift"

    def run(self, prog: Program) -> List[Finding]:
        out = []
        # structural: in/out leaf dtypes of the jitted step
        step = prog.engine.jit_train_step(donate=False)
        out_state, _ = jax.eval_shape(step, prog.state, prog.batch)
        in_leaves = jax.tree_util.tree_flatten_with_path(prog.state)[0]
        out_leaves = jax.tree.leaves(out_state)
        for (path, x), y in zip(in_leaves, out_leaves):
            if x.dtype != y.dtype:
                out.append(Finding(
                    pass_name=self.name, severity="error",
                    program=prog.name, op="state-leaf",
                    location=jax.tree_util.keystr(path),
                    message=f"state leaf dtype drifts across the step: "
                            f"{x.dtype} in, {y.dtype} out"))
        # census: no unexpected float down-casts; comm casts on the wire
        allowed = {"f32", prog.comm_mlir}
        for c in scoped_converts(prog.stablehlo_debug):
            if c.src in _MLIR_FLOATS and c.dst in _MLIR_QUANT:
                # a quantize cast: only legal as the declared comm_dtype
                # inside the wire scope (the reducers' quantize seam)
                if c.dst == prog.comm_mlir and not _in_wire_scope(c.scope):
                    out.append(Finding(
                        pass_name=self.name, severity="error",
                        program=prog.name, op=f"convert->{c.dst}",
                        location=c.scope,
                        message=f"quantize cast {c.src}->{c.dst} "
                                f"({c.elements} elements) outside the "
                                f"'wire' scope — a wire quantization "
                                f"leaked into compute"))
                continue
            if c.src not in _MLIR_FLOATS or c.dst not in _MLIR_FLOATS:
                continue
            if _MLIR_FLOATS[c.dst] >= _MLIR_FLOATS[c.src]:
                continue  # up-casts / same-width never lose precision
            if c.dst not in allowed:
                out.append(Finding(
                    pass_name=self.name, severity="error",
                    program=prog.name, op=f"convert->{c.dst}",
                    location=c.scope,
                    message=f"unexpected down-cast {c.src}->{c.dst} "
                            f"({c.elements} elements) — not the declared "
                            f"comm_dtype and not the compute dtype"))
            elif c.dst == prog.comm_mlir and c.dst != "f32" \
                    and not _in_wire_scope(c.scope):
                out.append(Finding(
                    pass_name=self.name, severity="error",
                    program=prog.name, op=f"convert->{c.dst}",
                    location=c.scope,
                    message=f"comm_dtype down-cast {c.src}->{c.dst} "
                            f"({c.elements} elements) outside the 'wire' "
                            f"scope — a wire cast leaked into compute"))
        return out


class FencePass:
    """Overlap-mode programs must (a) carry ``optimization_barrier``
    fences — the consume/issue seam the bitwise-equal-to-inline
    guarantee rests on (PR 7) — and (b) lower the SAME number of
    reduction ops as the inline sibling: the pipeline moves the reduce
    to the previous step's tail, it never duplicates or drops one."""

    name = "fence"

    def run(self, prog: Program) -> List[Finding]:
        if not prog.point.overlap:
            return []
        out = []
        n_fence = count_ops(prog.stablehlo, "optimization_barrier")
        if n_fence == 0:
            out.append(Finding(
                pass_name=self.name, severity="error", program=prog.name,
                op="optimization_barrier",
                message="overlap-mode step lowered without any "
                        "optimization_barrier — the consume/issue seam "
                        "is unfenced and XLA may refuse across it"))
        inline = prog.inline_sibling()
        r_pipe = count_ops(prog.stablehlo, "reduce")
        r_inline = count_ops(inline.stablehlo, "reduce")
        if r_pipe != r_inline:
            out.append(Finding(
                pass_name=self.name, severity="error", program=prog.name,
                op="stablehlo.reduce",
                message=f"pipelined step lowers {r_pipe} reduce ops vs "
                        f"{r_inline} inline — the overlap schedule "
                        f"duplicated or dropped a collective"))
        return out


class WireAccountingPass:
    """Cross-check the hand-written wire accounting against the lowered
    program: the comm_dtype down-cast bytes observed under the ``wire``
    scope must equal the reducer's ``wire_model()['cast_bytes']`` census,
    and ``Reducer.wire_bytes()`` (the bench column) must equal the same
    model's independently-written ``accounted_bytes`` — edit one side
    and the gate trips.  Error-feedback reducers additionally must not
    account more than the dense payload.  Skipped when ``comm_dtype`` is
    f32 (no observable wire cast to count)."""

    name = "wire-accounting"

    def run(self, prog: Program) -> List[Finding]:
        red = getattr(prog.alg, "reducer", None)
        if red is None or not hasattr(red, "wire_model"):
            return []
        it = Q.wire_itemsize(prog.cfg.comm_dtype)
        if it == 4:
            return []
        sizes = prog.wire_sizes
        model = red.wire_model(sizes, prog.n_workers)
        observed = sum(
            c.elements * _MLIR_WIRE[c.dst]
            for c in scoped_converts(prog.stablehlo_debug)
            if c.dst == prog.comm_mlir and c.src in _MLIR_FLOATS
            and _MLIR_WIRE[c.dst] < _MLIR_FLOATS[c.src]
            and _in_wire_scope(c.scope))
        out = []
        if observed != int(model["cast_bytes"]):
            out.append(Finding(
                pass_name=self.name, severity="error", program=prog.name,
                op="cast-census",
                message=f"lowered wire-scope comm_dtype casts move "
                        f"{observed} bytes but the reducer's wire_model "
                        f"predicts {int(model['cast_bytes'])} — the "
                        f"lowering and the model drifted apart"))
        accounted = int(red.wire_bytes(sizes))
        if accounted != int(model["accounted_bytes"]):
            out.append(Finding(
                pass_name=self.name, severity="error", program=prog.name,
                op="wire-bytes",
                message=f"Reducer.wire_bytes says {accounted} B/step but "
                        f"wire_model accounts {int(model['accounted_bytes'])}"
                        f" — the bench column no longer matches the "
                        f"hand accounting"))
        dense = sum(sizes) * it
        if not getattr(red, "stateless", True) and accounted > dense:
            out.append(Finding(
                pass_name=self.name, severity="error", program=prog.name,
                op="compression",
                message=f"compressed reducer accounts {accounted} B/step "
                        f"> dense payload {dense} B — compression that "
                        f"inflates the wire"))
        return out


PASSES = (DonationPass(), HostSyncPass(), RetracePass(), DtypeDriftPass(),
          FencePass(), WireAccountingPass())


# ---------------------------------------------------------------------------
# runners + CLI
# ---------------------------------------------------------------------------


def run_point(prog: Program,
              passes: Sequence = PASSES) -> List[Finding]:
    findings: List[Finding] = []
    for p in passes:
        findings.extend(p.run(prog))
    return findings


def run_grid(points: Optional[Sequence[GridPoint]] = None, *,
             model: str = "toy", passes: Sequence = PASSES,
             verbose: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    for point in (points if points is not None else iter_grid()):
        prog = Program(point, model=model)
        got = run_point(prog, passes)
        findings.extend(got)
        if verbose:
            print(f"[lint] {point.name:40s} "
                  f"{'OK' if not got else f'{len(got)} finding(s)'}",
                  file=sys.stderr)
    return findings


def run_ast(src_root="src") -> List[Finding]:
    from repro.analysis import astlint
    return astlint.lint_paths(src_root)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro-lint",
        description="static analyzer over the lowered train-step grid "
                    "(layer 1) and the source tree (layer 2)")
    ap.add_argument("--select", default="",
                    help="substring filter on grid-point names "
                         "(e.g. 'topk', 'dc_s3gd', '/ov')")
    ap.add_argument("--model", choices=("toy", "transformer"),
                    default="toy")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="write the repro.lint/v1 report here")
    ap.add_argument("--baseline", default=None,
                    help="committed baseline report; only NEW findings "
                         "gate the exit status")
    ap.add_argument("--write-baseline", dest="write_baseline",
                    default=None,
                    help="write the current findings as a baseline "
                         "report and exit 0")
    ap.add_argument("--no-ast", action="store_true",
                    help="skip the AST layer")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip the lowered-program layer")
    ap.add_argument("--src", default="src",
                    help="source root for the AST layer")
    ap.add_argument("--list", action="store_true",
                    help="print the grid and exit")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    points = [p for p in iter_grid() if args.select in p.name]
    if args.list:
        for p in points:
            print(p.name)
        return 0

    findings: List[Finding] = []
    if not args.no_hlo:
        findings.extend(run_grid(points, model=args.model,
                                 verbose=not args.quiet))
    if not args.no_ast:
        findings.extend(run_ast(args.src))

    meta = {"grid": [p.name for p in points], "model": args.model,
            "ast": not args.no_ast, "jax": jax.__version__,
            "backend": jax.default_backend()}
    report = findings_report(findings, meta)

    if args.write_baseline:
        Path(args.write_baseline).write_text(json.dumps(report, indent=2)
                                             + "\n")
        print(f"wrote baseline with {len(findings)} finding(s) to "
              f"{args.write_baseline}")
        return 0
    if args.json_path:
        Path(args.json_path).write_text(json.dumps(report, indent=2)
                                        + "\n")

    baseline = load_baseline(args.baseline) if args.baseline else set()
    fresh = new_findings(findings, baseline)
    suppressed = len(findings) - len(fresh)
    print(render_findings(fresh))
    if suppressed:
        print(f"({suppressed} baseline finding(s) suppressed)")
    return 1 if fresh else 0


if __name__ == "__main__":
    sys.exit(main())
