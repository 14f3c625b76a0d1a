"""Smoke run of the main paths on TPU at qwen3-0.6b's published widths.

    python chip_smoke.py               # one chip: train, serve, kernels
    python chip_smoke.py --four-chips  # W=4 DC-S3GD, one worker per chip

One process drives every phase through the entry points a user calls
(`repro.launch.train.run`, `repro.serve.Scheduler`, the Pallas kernels)
with random weights from a fixed seed.  Each phase prints one JSON line:
compile seconds, step or request wall times, losses or tokens, the
device's peak memory so far, and whether the lowered programs hold a
Pallas kernel (``tpu_custom_call``).  The last line is
``{"ok": true, "device": {...}}``.  Any failed check or phase error ends
the run with a non-zero exit and without that line, and so does a
machine whose first JAX device is not a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

ARCH = "qwen3-0.6b"
SEED = 0
IR_DIR = ROOT / ".smoke_ir"  # lowered programs of the running phase


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class Probe:
    """Per-phase measurement: backend compile seconds (JAX's own compile
    event, persistent-cache reads included), whether any program lowered
    inside the phase holds a Pallas TPU kernel, and the peak device
    memory afterwards."""

    _compile_s = 0.0

    @classmethod
    def _on_event(cls, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            cls._compile_s += duration

    def __enter__(self):
        gc.collect()  # the previous phase's arrays must be gone
        self.live_bytes_before = sum(a.nbytes for a in jax.live_arrays())
        shutil.rmtree(IR_DIR, ignore_errors=True)
        jax.config.update("jax_dump_ir_to", str(IR_DIR))
        self._c0 = Probe._compile_s
        return self

    def __exit__(self, *exc):
        jax.config.update("jax_dump_ir_to", None)
        self.compile_s = Probe._compile_s - self._c0
        self.tpu_custom_call = any(
            "tpu_custom_call" in p.read_text()
            for p in IR_DIR.glob("*.mlir"))
        shutil.rmtree(IR_DIR, ignore_errors=True)
        stats = [d.memory_stats() or {} for d in jax.devices()]
        self.peak_bytes = [s.get("peak_bytes_in_use") for s in stats]
        self.bytes_limit = [s.get("bytes_limit") for s in stats]
        return False

    def record(self) -> dict:
        return {"compile_s": self.compile_s,
                "tpu_custom_call": self.tpu_custom_call,
                "live_bytes_before": self.live_bytes_before,
                "peak_bytes_in_use": self.peak_bytes,
                "bytes_limit": self.bytes_limit}


# ---------------------------------------------------------------------------
# train: repro.launch.train.run at the published widths
# ---------------------------------------------------------------------------


def train(phase: str, flags: list, *, workers: int) -> dict:
    from repro.launch import train as T

    argv = ["--arch", ARCH, "--workers", str(workers),
            "--batch-per-worker", "4", "--seq", "512", "--steps", "5",
            "--log-every", "1", "--seed", str(SEED)] + flags
    with Probe() as probe:
        result = T.run(T.build_argparser().parse_args(argv))
    hist = result["history"]
    walls = [h["wall_s"] for h in hist]
    rec = {"phase": phase, "argv": argv,
           "losses": [h["loss"] for h in hist],
           "first_step_s": walls[0],
           "step_s": [b - a for a, b in zip(walls, walls[1:])],
           "state_devices": result["state_devices"], **probe.record()}
    emit(rec)
    check(all(math.isfinite(x) for x in rec["losses"]),
          f"{phase}: losses finite")
    return rec


def close(a: list, b: list, rtol: float) -> float:
    """Largest relative difference of two loss curves, checked against
    ``rtol``."""
    worst = max(abs(x - y) / abs(y) for x, y in zip(a, b))
    check(len(a) == len(b) and worst <= rtol,
          f"losses agree to {rtol} relative (worst {worst})")
    return worst


def train_phase() -> None:
    from repro.configs import get_config

    # the fused tail runs first, on an empty device: its bucket relayouts
    # leave the least headroom of any program here
    kern = train("train_kernels", ["--algo", "dc_s3gd", "--use-kernels",
                                   "--buckets", "8"], workers=1)
    xla = train("train_xla", ["--algo", "dc_s3gd"], workers=1)
    ln_v = math.log(get_config(ARCH).vocab_size)
    for rec in (kern, xla):
        check(abs(rec["losses"][0] - ln_v) < 0.5,
              f"{rec['phase']}: step-0 loss {rec['losses'][0]} within 0.5 "
              f"of ln(vocab) = {ln_v}")
    check(kern["tpu_custom_call"], "fused tail lowers to Pallas kernels")
    emit({"phase": "train_compare", "ln_vocab": ln_v,
          "max_rel_loss_diff": close(kern["losses"], xla["losses"], 1e-3)})


# ---------------------------------------------------------------------------
# serve: continuous batching over the paged KV cache
# ---------------------------------------------------------------------------


def serve(phase: str, use_kernel: bool) -> tuple:
    from repro.configs import get_config
    from repro.models.transformer import Model
    from repro.serve import Request, Scheduler

    cfg = get_config(ARCH)
    rng = np.random.default_rng(SEED)
    # 8 requests, prompts of 64..256 tokens in equal-length pairs (one
    # batched prefill per pair), 32 new tokens each, greedy
    lens = [64, 64, 128, 128, 192, 192, 256, 256]
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n).tolist(),
                    max_new=32) for i, n in enumerate(lens)]
    with Probe() as probe:
        model = Model(cfg, remat=False, scan_chunk=64)
        params = model.init(jax.random.PRNGKey(SEED))
        sch = Scheduler(model, params, slots=8, pages=161, page_size=16,
                        max_len=320, decode_burst=4, use_kernel=use_kernel)
        t0 = time.perf_counter()
        done = sch.run(reqs)
        wall = time.perf_counter() - t0
        summary = sch.latency_summary()
        del model, params, sch
    outs = {r.rid: list(r.out) for r in done}
    emit({"phase": phase, "requests": len(done), "wall_s": wall,
          "tokens": summary["tokens"],
          "request_s": [r.t_done - r.t_submit
                        for r in sorted(done, key=lambda r: r.rid)],
          "p50_token_latency_s": summary.get("p50_token_latency_s"),
          "first_tokens": [outs[i][0] for i in sorted(outs)],
          **probe.record()})
    check(sorted(outs) == list(range(len(reqs)))
          and all(len(o) == 32 for o in outs.values()),
          f"{phase}: every request finishes with 32 tokens")
    return outs, probe.tpu_custom_call


def serve_phase() -> None:
    gather, _ = serve("serve_xla_gather", use_kernel=False)
    paged, kernel_in = serve("serve_paged_kernel", use_kernel=True)
    check(kernel_in, "paged decode lowers to the Pallas kernel")
    firsts = all(gather[i][0] == paged[i][0] for i in gather)
    same = sum(a == b for i in gather for a, b in zip(gather[i], paged[i]))
    total = sum(len(o) for o in gather.values())
    emit({"phase": "serve_compare", "first_tokens_match": firsts,
          "token_match_share": same / total})
    check(firsts, "first token of every request matches across paths")


# ---------------------------------------------------------------------------
# kernels: paged attention against its oracle on the chip
# ---------------------------------------------------------------------------


def kernel_phase() -> None:
    from repro.configs import get_config
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref

    cfg = get_config(ARCH)
    KV, G, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, \
        cfg.resolved_head_dim
    B, ps, mp = 8, 32, 12
    n_pages = B * mp + 1
    kq, kk, kv, ks, vs, kp = jax.random.split(jax.random.PRNGKey(SEED), 6)
    q = jax.random.normal(kq, (B, KV, G, hd), jnp.bfloat16)
    bt = jax.random.permutation(kp, n_pages - 1)[:B * mp].reshape(B, mp) + 1
    lengths = jnp.asarray(np.random.default_rng(SEED).integers(
        1, mp * ps + 1, B), jnp.int32)
    pool = (n_pages, ps, KV, hd)
    cases = {
        "bf16_pages32": (jax.random.normal(kk, pool, jnp.bfloat16),
                         jax.random.normal(kv, pool, jnp.bfloat16)),
        "int8_pages32": (
            jax.random.randint(kk, pool, -127, 128, jnp.int8),
            jax.random.randint(kv, pool, -127, 128, jnp.int8),
            jax.random.uniform(ks, pool[:2], jnp.float32, 0.5, 1.5) / 127,
            jax.random.uniform(vs, pool[:2], jnp.float32, 0.5, 1.5) / 127),
    }

    def kernel(q, bt, ln, k, v, k_scale=None, v_scale=None):
        return paged_attention(q, k, v, bt, ln, k_scale=k_scale,
                               v_scale=v_scale)

    for name, kv_args in cases.items():
        with Probe() as probe:
            compiled = jax.jit(kernel).lower(q, bt, lengths,
                                             *kv_args).compile()
            t0 = time.perf_counter()
            out = jax.block_until_ready(compiled(q, bt, lengths, *kv_args))
            wall = time.perf_counter() - t0
            k, v, *scales = kv_args
            ref = paged_attention_ref(q, k, v, bt, lengths, *scales)
        err = float(jnp.max(jnp.abs(out - ref)))
        scale = float(jnp.max(jnp.abs(ref)))
        emit({"phase": f"kernel_{name}", "shape": [B, KV, G, hd, ps, mp],
              "call_s": wall, "max_abs_err": err, "max_abs_ref": scale,
              "compiled_has_kernel":
                  "tpu_custom_call" in compiled.as_text(),
              **probe.record()})
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: compiled text holds the kernel")
        # bf16 tolerance: the oracle's einsums run at the chip's default
        # (bf16-pass) matmul precision
        check(err <= 2e-2 * max(scale, 1.0),
              f"{name}: kernel matches oracle ({err} vs {scale})")


# ---------------------------------------------------------------------------
# four chips: W=4 DC-S3GD, one worker per chip
# ---------------------------------------------------------------------------


def four_chip_phase() -> None:
    n = len(jax.devices())
    check(n == 4, f"--four-chips needs 4 devices, found {n}")
    inline = train("w4_dc_s3gd_inline", ["--algo", "dc_s3gd",
                                         "--buckets", "8"], workers=4)
    overlap = train("w4_dc_s3gd_overlap", ["--algo", "dc_s3gd",
                                           "--buckets", "8", "--overlap"],
                    workers=4)
    ssgd = train("w4_ssgd", ["--algo", "ssgd"], workers=4)
    for rec in (inline, overlap, ssgd):
        check(rec["state_devices"] == 4,
              f"{rec['phase']}: every TrainState leaf spans 4 devices")
        check(all(p < lim for p, lim in zip(rec["peak_bytes_in_use"],
                                            rec["bytes_limit"])),
              f"{rec['phase']}: each chip's peak under its HBM")
    # the two schedules make the same reductions of the same payloads
    # (bitwise on CPU); on the chip the two programs' all-reduces need not
    # round alike, so the losses are held to f32 rounding
    emit({"phase": "w4_compare",
          "overlap_equals_inline": overlap["losses"] == inline["losses"],
          "overlap_max_rel_diff": close(overlap["losses"],
                                        inline["losses"], 1e-6),
          "ssgd_step0": ssgd["losses"][0],
          "dc_s3gd_step0": inline["losses"][0],
          "step0_equal": ssgd["losses"][0] == inline["losses"][0]})
    check(abs(ssgd["losses"][0] - inline["losses"][0])
          <= 1e-6 * abs(inline["losses"][0]),
          "SSGD step-0 loss equals DC-S3GD's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the W=4 one-worker-per-chip phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    emit({"cache_dir": use_compile_cache(), "jax": jax.__version__})
    jax.monitoring.register_event_duration_secs_listener(Probe._on_event)

    if args.four_chips:
        four_chip_phase()
    else:
        train_phase()
        serve_phase()
        kernel_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
