"""Serving CLI: thin driver over the ``repro.serve`` subsystem.

The request loop itself lives in ``repro.serve`` (docs/serving.md): a
continuous-batching scheduler with slot-based KV-cache admission —
finished rows are retired and queued requests admitted *per decode step*
(single-row prefill scattered into the freed slot; surviving rows are
never re-prefilled), with per-row position vectors so left-padded short
prompts decode at their true positions.  ``--scheduler static`` selects
the legacy static-batch loop (the measured baseline).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 16 --batch 4 --gen 32

``--loop open`` switches to arrival-clocked admission: requests are
drawn from a ``--workload`` preset with real arrival times and only
become admissible once the (virtual or wall) clock passes them, with a
pluggable ``--policy`` (static / slo-adaptive / reject) deciding
admission and the pool's accuracy tier per step:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --loop open --workload bursty --policy slo-adaptive \
      --slo-ttft-ms 50 --requests 64 --batch 4 --gen 8

``serve_loop`` and ``ServeStats`` stay importable here for backward
compatibility; ``serve_loop`` now delegates to
:func:`repro.serve.static_serve_loop` over a synthesized queue.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs.registry import apply_approx, get_config
from repro.distributed.sharding import data_parallel_mesh
from repro.engine import config as engine_config
from repro.engine import modes as engine_modes
from repro.models.registry import build_model
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import (
    SelfSpeculative,
    ServeStats,
    continuous_serve_loop,
    get_policy,
    static_serve_loop,
    supports_continuous,
    synth_requests,
)
from repro.serve.policy import POLICIES
from repro.serve.stats import percentile
from repro.serve.workload import PRESETS, generate, preset_spec

__all__ = ["ServeStats", "serve_loop", "main"]


def serve_loop(
    model,
    params,
    *,
    requests: int = 16,
    batch_size: int = 4,
    prompt_len: int = 32,
    gen: int = 32,
    seed: int = 0,
) -> ServeStats:
    """Legacy entry point: static-batch loop over a synthesized queue.

    Kept for existing callers; new code should build a request list
    (``repro.serve.synth_requests`` or real prompts) and call
    ``static_serve_loop`` / ``continuous_serve_loop`` directly.
    """
    queue = synth_requests(
        requests, prompt_len=prompt_len, gen=gen,
        vocab_size=model.cfg.vocab_size, seed=seed, vary_budget=False,
    )
    result = static_serve_loop(
        model, params, queue,
        batch_size=batch_size, prompt_len=prompt_len, gen=gen,
        seed=seed, warmup=False,
    )
    return result.stats


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--approx-mode", default=None, choices=engine_modes.list_modes())
    ap.add_argument("--quality-tier", default=None,
                    choices=engine_config.list_tiers(),
                    help="accuracy tier for the run: the engine.config "
                         "controller resolves each GEMM class to the cheapest "
                         "splitting point meeting the tier's error budget; "
                         "requests are tagged with the tier and checked at "
                         "admission (mutually exclusive with --approx-mode)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scheduler", default=None,
                    choices=("continuous", "static"),
                    help="continuous: per-step retirement/admission (the default "
                         "where supported); static: the legacy re-batch-at-drain "
                         "loop (auto-selected for encoder-decoder and "
                         "recurrent-state archs, which continuous rejects)")
    ap.add_argument("--vary-budget", action="store_true",
                    help="draw per-request budgets in [1, gen] instead of gen")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="retire a row early when it emits this token id")
    ap.add_argument("--data-parallel", action="store_true",
                    help="shard the decode batch over a ('data',) device mesh "
                         "when multiple devices are available")
    ap.add_argument("--loop", default="closed", choices=("closed", "open"),
                    help="closed: drain a pre-filled queue (the legacy mode); "
                         "open: arrival-clocked admission — requests become "
                         "admissible only once their workload arrival time "
                         "passes (continuous scheduler only)")
    ap.add_argument("--workload", default="bursty", choices=sorted(PRESETS),
                    help="open loop: traffic preset supplying the arrival "
                         "clock and length tails (ignored for --loop closed)")
    ap.add_argument("--policy", default=None, choices=sorted(POLICIES),
                    help="admission policy for --loop open: static keeps the "
                         "bit-match oracle, slo-adaptive degrades the pool "
                         "tier under load, reject sheds when the queue grows")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="stamp a TTFT SLO (ms) on every open-loop request; "
                         "enables slo attainment in the summary")
    ap.add_argument("--step-time-ms", type=float, default=10.0,
                    help="virtual-clock cost of one exact decode step (open "
                         "loop; tiers scale it by their cycle factor)")
    ap.add_argument("--clock", default="virtual", choices=("virtual", "wall"),
                    help="open loop: deterministic virtual clock (default) or "
                         "real sleeping wall clock")
    ap.add_argument("--strategy", default="greedy",
                    choices=("greedy", "speculative"),
                    help="decode strategy (continuous scheduler only): greedy "
                         "one-token rounds, or self-speculative rounds — k "
                         "draft-tier proposal steps verified by one batched "
                         "forward on the verify tier; output bit-matches "
                         "greedy decode on the verify engine")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative: draft tokens proposed per round")
    ap.add_argument("--draft-tier", default="draft",
                    choices=engine_config.list_tiers(),
                    help="speculative: accuracy tier proposing draft tokens")
    ap.add_argument("--verify-tier", default=None,
                    choices=engine_config.list_tiers(),
                    help="speculative: tier whose engine verifies (default: "
                         "the pool's own tier)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.approx_mode and args.quality_tier:
        ap.error("--approx-mode and --quality-tier are mutually exclusive "
                 "(the tier owns the mode)")
    if args.approx_mode:
        cfg = apply_approx(cfg, mode=args.approx_mode)
    if args.quality_tier:
        print(f"# {engine_config.resolve_tier(args.quality_tier).describe()}")

    scheduler = args.scheduler
    if scheduler is None:
        scheduler = "continuous" if supports_continuous(cfg) else "static"
        if scheduler == "static":
            print(f"# {cfg.name}: auto-selected --scheduler static "
                  f"(continuous supports attention and SSD decoder stacks; "
                  f"RG-LRU layers absorb left pads, encoder-decoders have no slot cache)")
    if args.data_parallel and scheduler != "continuous":
        ap.error("--data-parallel only applies to --scheduler continuous")
    if args.loop == "open" and scheduler != "continuous":
        ap.error("--loop open requires --scheduler continuous")
    if args.policy is not None and args.loop != "open":
        ap.error("--policy only applies to --loop open (closed-loop "
                 "admission is the implicit static policy)")
    if args.strategy == "speculative" and scheduler != "continuous":
        ap.error("--strategy speculative requires --scheduler continuous")

    strategy = None
    if args.strategy == "speculative":
        strategy = SelfSpeculative(
            k=args.spec_k, draft_tier=args.draft_tier,
            verify_tier=args.verify_tier,
        )
        verify = args.verify_tier or args.quality_tier or "exact"
        est = engine_config.accept_rate_estimate(args.draft_tier, verify)
        print(f"# speculative: k={args.spec_k} draft={args.draft_tier} "
              f"verify={verify}, accept-rate lower bound {est:.1%} "
              f"(engine_config.accept_rate_estimate)")

    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))

    run_kwargs = {}
    if args.loop == "open":
        spec = preset_spec(
            args.workload, requests=args.requests, prompt_len=args.prompt_len,
            max_new=args.gen, vocab_size=cfg.vocab_size,
            slo_ttft_s=args.slo_ttft_ms / 1e3 if args.slo_ttft_ms else None,
        )
        draw = generate(spec, seed=args.seed)
        queue = list(draw.requests)
        run_kwargs = dict(
            arrivals_s=list(draw.arrivals_s),
            policy=get_policy(args.policy or "static"),
            step_time_s=args.step_time_ms / 1e3,
            clock=args.clock,
        )
        print(f"# open loop: {args.workload} preset, offered "
              f"{draw.offered_rps:.1f} rps, policy "
              f"{run_kwargs['policy'].name}")
    else:
        queue = synth_requests(
            args.requests, prompt_len=args.prompt_len, gen=args.gen,
            vocab_size=cfg.vocab_size, seed=args.seed,
            vary_budget=args.vary_budget, eos_id=args.eos_id,
            quality=args.quality_tier,
        )
    if scheduler == "continuous":
        mesh = data_parallel_mesh(args.batch) if args.data_parallel else None
        result = continuous_serve_loop(
            model, params, queue,
            batch_size=args.batch, prompt_len=args.prompt_len,
            max_new=args.gen, mesh=mesh, quality=args.quality_tier,
            strategy=strategy, **run_kwargs,
        )
    else:
        result = static_serve_loop(
            model, params, queue,
            batch_size=args.batch, prompt_len=args.prompt_len, gen=args.gen,
            seed=args.seed, quality=args.quality_tier,
        )
    print(result.stats.summary())
    ar = result.stats.accept_rate
    if ar is not None:
        print(f"# speculative accept: {result.stats.spec_accepted}/"
              f"{result.stats.spec_proposed} draft tokens ({ar:.1%}), "
              f"{result.stats.spec_rolled_back} rolled back over "
              f"{result.stats.spec_rounds} speculated rounds")
    lat = result.stats.request_latencies_s
    if lat:
        print(
            f"per-request latency p50 {1e3 * percentile(lat, 50):.0f}ms "
            f"p95 {1e3 * percentile(lat, 95):.0f}ms over "
            f"{len(lat)} requests"
        )
    for sw in result.tier_switches:
        print(f"# tier switch @ step {sw.step} t={sw.now_s:.3f}s: "
              f"{sw.from_tier} -> {sw.to_tier} ({sw.reason})")


if __name__ == "__main__":
    main()
