"""Smoke test of the serving path on a TPU: qwen3-0.6b at its published
widths, every quality tier on native Pallas kernels.

    python chip_smoke.py               # phases (a) and (b), one chip
    python chip_smoke.py --four-chips  # phase (c) only, four chips

(a) Kernels.  Each fused GEMM kernel (``lut_matmul`` for ``bitexact``,
    ``seqmul_matmul``, ``packed_matmul`` for ``inject``,
    ``lowrank_matmul``) runs natively at qwen3-0.6b's MLP shapes and is
    compared with its mode's reference body.
(b) Serve.  ``ContinuousScheduler`` at the full config serves 16
    requests (batch 8, prompt bucket 128, 32 new tokens) once per tier
    ``exact``, ``balanced`` and ``draft``.  Every request must get its
    budget of in-vocabulary tokens, and on ``exact`` the tokens must equal
    ``static_serve_loop`` on the same queue.
(c) ``--four-chips``: the ``exact`` tier decoding on a 4-device
    ``("data",)`` mesh must give the tokens of the same queue served on
    one device, with the pool batch split over the four devices.

Tolerances of (a) are those of ``tests/test_fused_kernels.py`` (bit-exact
for the integer modes, ``rtol = atol = 2e-6`` for ``lowrank``), widened by
the f32 rounding a K-term sum allows: at these widths a sum of integer
products passes 2^24, and each of the two sums compared may round by up
to ``(terms - 1) * 2^-24 * sum(|term|)`` in any order of summation.

Tokens/s and time to first token are printed as information only.
Everything runs in one process.  The script exits non-zero, without its
final JSON line, when the platform is not a TPU, when Pallas kernels
would run in interpret mode (``REPRO_FORCE_INTERPRET`` included), or when
any phase fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-0.6b"
MLP_SHAPES = ((1024, 3072), (3072, 1024))  # (K, N) of w1/w3 and w2
KERNEL_ROWS = (8, 128)  # a decode batch and a prompt bucket
KERNEL_MODES = ("bitexact", "seqmul", "inject", "lowrank")
TIERS = ("exact", "balanced", "draft")
REQUESTS, BATCH, PROMPT, MAX_NEW = 16, 8, 128, 32
F32_UNIT = 2.0 ** -24
_CUBE_CHUNK = 8  # rows per reference chunk: the (rows, K, N) cube stays small


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------- (a) kernels
def reference_and_bound(mode, x, w, *, n, t, rank, key):
    """The mode's reference body and, per element, the sum of |terms| its
    K-long sums add up (for the f32 rounding bound).

    ``bitexact``/``seqmul`` run their integer oracle on row chunks of the
    operands quantized once, which is the reference body's own arithmetic
    without the whole (M, K, N) product cube in memory at once."""
    import jax
    import jax.numpy as jnp

    from repro import engine
    from repro.engine import artifacts, modes

    (mx, sx), (mw, sw), scale = modes.quantize_operands(x, w, n)
    if mode in ("bitexact", "seqmul"):
        oracle = modes.bitexact_gemm_int if mode == "bitexact" else modes.seqmul_gemm_int
        rows = x.shape[0]

        def chunked(sa, sb):
            def one(args):
                ma, s = args
                return oracle(ma, s, mw, sb, n=n, t=t)

            out = jax.lax.map(one, (mx.reshape(rows // _CUBE_CHUNK, _CUBE_CHUNK, -1),
                                    sa.reshape(rows // _CUBE_CHUNK, _CUBE_CHUNK, -1)))
            return out.reshape(rows, -1)

        ref = chunked(sx, sw) * scale
        bound = chunked(jnp.ones_like(sx), jnp.ones_like(sw)) * scale
        return ref, bound, x.shape[1]
    kw = dict(n=n, t=t, mode=mode, rank=rank, backend="reference")
    if engine.get_mode(mode).needs_key:
        kw["key"] = key
    ref = engine.matmul(x, w, **kw)
    ax = mx.astype(jnp.float32)
    aw = mw.astype(jnp.float32)
    bound = ax @ aw
    terms = x.shape[1]
    if mode == "lowrank":
        u, v, _ = artifacts.svd_factors(n, t, rank)
        bound = bound + jnp.einsum("ikr,kjr->ij", jnp.abs(u[mx.astype(jnp.int32)]),
                                   jnp.abs(v[mw.astype(jnp.int32)]))
        terms *= 1 + rank
    else:  # inject: the quantized GEMM plus its noise draw
        (noise,) = modes.get_mode("inject").prepare(x, w, modes.GemmParams(n, t, True, rank), key)
        bound = bound + jnp.abs(noise)
    return ref, bound * scale, terms


def phase_kernels(*, rows=KERNEL_ROWS, shapes=MLP_SHAPES, seed=0) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import engine

    sel = next(q for q in engine.resolve_tier("balanced").per_target if q.target == "mlp")
    n, t, rank = sel.n, sel.t, 8
    key = jax.random.PRNGKey(seed)
    native = jax.default_backend() == "tpu"  # off the chip, Pallas interprets
    for mode in KERNEL_MODES:
        base = 2e-6 if mode == "lowrank" else 0.0
        for m in rows:
            for k_dim, n_dim in shapes:
                kx, kw_ = jax.random.split(jax.random.fold_in(key, m * k_dim + n_dim))
                x = jax.random.normal(kx, (m, k_dim), jnp.float32)
                w = jax.random.normal(kw_, (k_dim, n_dim), jnp.float32) * k_dim ** -0.5
                kw = dict(n=n, t=t, mode=mode, rank=rank, backend="pallas")
                if engine.get_mode(mode).needs_key:
                    kw["key"] = key
                t0 = time.perf_counter()
                fn = jax.jit(lambda a, b, kw=kw: engine.matmul(a, b, **kw)).lower(x, w).compile()
                compile_s = time.perf_counter() - t0
                if native and "tpu_custom_call" not in fn.as_text():
                    raise AssertionError(f"{mode}: no Pallas kernel in the compiled program")
                t0 = time.perf_counter()
                got = np.asarray(jax.block_until_ready(fn(x, w)))
                run_s = time.perf_counter() - t0
                with jax.default_matmul_precision("highest"):
                    ref, bound, terms = jax.jit(
                        lambda a, b, mode=mode: reference_and_bound(
                            mode, a, b, n=n, t=t, rank=rank, key=key))(x, w)
                ref, bound = np.asarray(ref), np.asarray(bound)
                tol = base + base * np.abs(ref) + 2.0 * (terms - 1) * F32_UNIT * bound
                err = np.abs(got - ref)
                ratio = float(np.max(err / np.maximum(tol, 1e-30)))
                log(f"# (a) {mode:8s} M={m:3d} K={k_dim} N={n_dim}: compile "
                    f"{compile_s:.2f}s, first run {run_s:.3f}s, max|err| "
                    f"{float(err.max()):.3e}, max err/tol {ratio:.3f}")
                if not (np.isfinite(got).all() and got.shape == ref.shape):
                    raise AssertionError(f"{mode} M={m}: non-finite or misshaped output")
                if np.any(err > tol):
                    raise AssertionError(
                        f"{mode} M={m} K={k_dim} N={n_dim}: kernel differs from its "
                        f"reference beyond tolerance (max err/tol {ratio:.3f})")


# --------------------------------------------------------------- (b) serve
def make_queue(vocab: int, *, requests: int, prompt_len: int, max_new: int,
               quality=None, seed: int = 0):
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(seed)
    return [
        Request(id=i, tokens=rng.integers(0, vocab, prompt_len).astype(np.int32),
                max_new=max_new, quality=quality)
        for i in range(requests)
    ]


def check_outputs(result, queue, vocab: int, label: str) -> None:
    import numpy as np

    for r in queue:
        toks = np.asarray(result.outputs[r.id])
        if len(toks) != r.max_new:
            raise AssertionError(f"{label}: request {r.id} got {len(toks)} of "
                                 f"{r.max_new} tokens")
        if toks.min() < 0 or toks.max() >= vocab:
            raise AssertionError(f"{label}: request {r.id} has a token outside "
                                 f"[0, {vocab})")


def _last_logits(model, params, tokens, batch: int):
    """f32 logits after ``tokens``, prefilled as row 0 of a batch of copies."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.train.steps import make_prefill_step

    toks = jnp.asarray(np.tile(np.asarray(tokens, np.int32)[None], (batch, 1)))
    pos = jnp.broadcast_to(jnp.arange(toks.shape[1], dtype=jnp.int32)[None], toks.shape)
    _, logits = jax.jit(make_prefill_step(model, toks.shape[1]))(
        params, {"tokens": toks, "positions": pos})
    return np.asarray(logits[0, -1].astype(jnp.float32))


def assert_same_tokens(model, params, ref, got, queue, label: str) -> int:
    """Greedy streams of ``got`` must equal those of ``ref``, request by request.

    bf16 rounding on the chip depends on the shapes a program runs at
    (a batch-1 admission prefill against a batch-8 one, 2 rows per device
    against 8), so two correct schedulers can pick different tokens where
    the two best logits are closer than that rounding.  A divergence is
    accepted only at such a tie: on the common prefix, the logits of the
    two candidates must differ by at most twice the change the same logits
    show between a batch-1 and a batch-8 prefill.  The rest of that stream
    is then not compared.  Returns the number of accepted ties."""
    import numpy as np

    ties = 0
    for r in queue:
        x, y = np.asarray(ref.outputs[r.id]), np.asarray(got.outputs[r.id])
        if x.shape != y.shape:
            raise AssertionError(f"{label}: request {r.id} has {len(y)} tokens, "
                                 f"the reference {len(x)}")
        if np.array_equal(x, y):
            continue
        i = int(np.argmax(x != y))
        prefix = np.concatenate([r.tokens, x[:i]])
        one = _last_logits(model, params, prefix, 1)
        noise = float(np.abs(one - _last_logits(model, params, prefix, BATCH)).max())
        gap = abs(float(one[x[i]] - one[y[i]]))
        log(f"# {label}: request {r.id} diverges at token {i} ({x[i]} vs {y[i]}): "
            f"logit gap {gap:.3e}, batch-shape rounding {noise:.3e}")
        if gap > 2.0 * noise:
            raise AssertionError(f"{label}: request {r.id} diverges at token {i} where "
                                 f"the logits are not tied: {x.tolist()} vs {y.tolist()}")
        ties += 1
    return ties


def exact_model(cfg):
    from repro.engine.config import apply_quality
    from repro.models.registry import build_model

    return build_model(apply_quality(cfg, "exact"))


def build(cfg, seed: int):
    import jax

    from repro.models.registry import build_model

    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init_params)(jax.random.PRNGKey(seed)))
    log(f"# model {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, heads {cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim}, "
        f"vocab {cfg.vocab_size}, {cfg.dtype}; params in "
        f"{time.perf_counter() - t0:.1f}s")
    return model, params


def serve_tier(model, params, queue, tier, *, batch, prompt_len, max_new, mesh=None):
    from repro.serve import ContinuousScheduler

    sched = ContinuousScheduler(model, params, batch_size=batch, prompt_len=prompt_len,
                                max_new=max_new, quality=tier, mesh=mesh)
    t0 = time.perf_counter()
    sched.warmup()
    compile_s = time.perf_counter() - t0
    result = sched.run(queue, warmup=False)
    st = result.stats
    ttft = sorted(st.ttft_s)
    log(f"# (b) tier {tier}: compile+warmup {compile_s:.1f}s, {st.tokens_out} tokens in "
        f"{st.wall_s:.2f}s ({st.tokens_out / st.wall_s:.1f} tok/s, information only), "
        f"TTFT p50 {ttft[len(ttft) // 2]:.3f}s, {st.decode_steps} decode steps")
    return sched, result


def phase_serve(cfg, *, seed=0, tiers=TIERS, requests=REQUESTS, batch=BATCH,
                prompt_len=PROMPT, max_new=MAX_NEW) -> None:
    from repro.serve import static_serve_loop

    model, params = build(cfg, seed)
    for tier in tiers:
        queue = make_queue(cfg.vocab_size, requests=requests, prompt_len=prompt_len,
                           max_new=max_new, quality=tier, seed=seed)
        _, result = serve_tier(model, params, queue, tier, batch=batch,
                               prompt_len=prompt_len, max_new=max_new)
        check_outputs(result, queue, cfg.vocab_size, f"tier {tier}")
        if tier == "exact":
            t0 = time.perf_counter()
            static = static_serve_loop(model, params, queue, batch_size=batch,
                                       prompt_len=prompt_len, gen=max_new, quality=tier)
            ties = assert_same_tokens(exact_model(cfg), params, static, result, queue,
                                      "exact vs static_serve_loop")
            log(f"# (b) tier exact: tokens equal static_serve_loop but for {ties} "
                f"tie(s) ({time.perf_counter() - t0:.1f}s incl. compile)")


# --------------------------------------------------------- (c) four chips
def phase_four_chips(cfg, *, seed=0, requests=REQUESTS, batch=BATCH,
                     prompt_len=PROMPT, max_new=MAX_NEW) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.sharding import data_parallel_mesh, mesh_context

    devices = jax.devices()
    if len(devices) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, found {len(devices)}")
    mesh = data_parallel_mesh(batch, devices=devices[:4])
    if mesh is None or mesh.devices.size != 4:
        raise AssertionError(f"no 4-device data mesh for batch {batch}")
    model, params = build(cfg, seed)
    queue = make_queue(cfg.vocab_size, requests=requests, prompt_len=prompt_len,
                       max_new=max_new, quality="exact", seed=seed)
    _, one = serve_tier(model, params, queue, "exact", batch=batch,
                        prompt_len=prompt_len, max_new=max_new)
    sched, dp = serve_tier(model, params, queue, "exact", batch=batch,
                           prompt_len=prompt_len, max_new=max_new, mesh=mesh)
    check_outputs(dp, queue, cfg.vocab_size, "data-parallel")
    # the pool's KV cache must be split over the four devices along batch
    toks = jnp.asarray(np.stack([r.tokens for r in queue[:batch]]))
    pos = jnp.broadcast_to(jnp.arange(prompt_len, dtype=jnp.int32)[None], toks.shape)
    with mesh_context(mesh):
        caches, _ = sched.engine_for(None).prefill_pool(params, toks, pos)
    leaf = jax.tree_util.tree_leaves(caches)[0]
    shard_rows = {s.data.shape[leaf.ndim - 4] if leaf.ndim >= 4 else None
                  for s in leaf.addressable_shards}
    used = {s.device for s in leaf.addressable_shards}
    log(f"# (c) cache leaf {leaf.shape} on {len(used)} devices, "
        f"spec {leaf.sharding.spec}, per-shard batch {sorted(shard_rows)}")
    if len(used) != 4 or shard_rows != {batch // 4}:
        raise AssertionError(f"pool cache not split over 4 devices: {leaf.sharding}")
    ties = assert_same_tokens(exact_model(cfg), params, one, dp, queue,
                              "4-device data-parallel vs one device")
    log(f"# (c) 4-device data-parallel tokens equal the one-device run but for "
        f"{ties} tie(s)")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase (c): data-parallel decode on 4 chips")
    ap.add_argument("--seed", type=int, default=0, help="weights, prompts and operands")
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    from repro.configs.registry import get_config
    from repro.engine.policy import use_interpret

    devices = jax.devices()
    dev = devices[0]
    log(f"# jax {jax.__version__}; {len(devices)} devices: {dev.platform} "
        f"{dev.device_kind}; compile cache {cache}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 2
    if use_interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode "
              "(REPRO_FORCE_INTERPRET is set)", file=sys.stderr)
        return 2
    cfg = get_config(ARCH)
    phases = ([("c", lambda: phase_four_chips(cfg, seed=args.seed))] if args.four_chips
              else [("a", lambda: phase_kernels(seed=args.seed)),
                    ("b", lambda: phase_serve(cfg, seed=args.seed))])
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            print(f"chip_smoke: phase ({name}) failed", file=sys.stderr)
            return 1
        log(f"# phase ({name}) passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
