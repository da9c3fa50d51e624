"""Serving launcher: continuous-batching generation for any --arch
(``--smoke`` for the reduced config on CPU; published widths on a TPU).

Requests stream through the genserve engine: at most --wave sequences
decode concurrently, finished slots (EOS or budget) are recycled via
prefill injection — one-shot whole-prompt admission by default, or
*chunked prefill* (--prefill-chunk N: mixed wave-steps that ingest up to
N prompt tokens per round alongside decode, so a long prompt never
stalls the wave).  --page-size switches the KV cache to the paged
page-pool layout and --prefix-cache adds radix prefix reuse across
requests: admission matches each prompt against a prefix tree and skips
prefill on the cached prefix (--shared-prompts S makes the first S
prompt tokens identical across requests so the cache has something to
hit).  The report includes tokens/s, time-to-first-token p50/p95 (the
headline metric chunked prefill and prefix reuse move), the prefix-
cache token hit rate with the prefill tokens skipped, and the measured
mean decode-wave occupancy next to the cost model's ideal.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \
        --batch 16 --wave 4 --prompt-len 32 --new-tokens 16 --prefill-chunk 8
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --batch 16 --wave 4 --prompt-len 32 --new-tokens 16 \
        --prefill-chunk 8 --page-size 8 --prefix-cache --shared-prompts 24
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import archs
from repro.core.plan import decode_wave, predicted_occupancy, prefill_rounds
from repro.genserve import adapter as genserve
from repro.genserve.adapter import ttft_quantiles
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.rl.rollout import SamplerConfig


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--wave", type=int, default=0,
                    help="decode slots (0 = core.plan.decode_wave(batch))")
    ap.add_argument("--decode-chunk", type=int, default=4,
                    help="decode steps per host round")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked admission: prompt tokens ingested per "
                         "mixed wave round (0 = one-shot prefill)")
    ap.add_argument("--eos-token", type=int, default=None,
                    help="retire sequences on this token id")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache: tokens per pool page "
                         "(0 = contiguous per-slot cache)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix reuse across requests (skips "
                         "prefill on cached prompt prefixes; implies "
                         "--page-size 8 unless given)")
    ap.add_argument("--shared-prompts", type=int, default=0,
                    help="make the first N prompt tokens identical "
                         "across requests (a shared system prompt)")
    ap.add_argument("--expect-prefix-hits", action="store_true",
                    help="exit nonzero unless the prefix-cache token "
                         "hit rate is > 0 (CI smoke)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft tokens proposed "
                         "per wave round, verified by one batched "
                         "(k+1)-wide target step (0 = off)")
    ap.add_argument("--draft-arch", default="",
                    help="configs.archs entry for the speculative "
                         "draft model (vocab/dtype follow --arch); "
                         "required with --spec-k")
    ap.add_argument("--check-spec-parity", action="store_true",
                    help="greedy only: also run the non-speculative "
                         "engine and exit nonzero unless the emitted "
                         "tokens match exactly (CI smoke)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="write a Chrome-trace JSON of the serving run "
                         "(view in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics", default=None, metavar="FILE",
                    help="dump the metrics-registry snapshot as JSON")
    return ap


def run_serve(args):
    """Serve ``args.batch`` requests through genserve and report; returns
    the timed run's rollout dict and engine stats."""
    if args.trace:
        obs_trace.enable()
    if args.prefix_cache and args.page_size == 0:
        args.page_size = 8
    if args.page_size and args.prefill_chunk == 0:
        # paged admission is chunked by construction
        args.prefill_chunk = min(args.prompt_len, 16)

    cfg = archs.get(args.arch, smoke=args.smoke)
    if cfg.is_encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving "
                         f"(run classification via launch.train instead)")
    mesh = make_host_mesh()
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    draft_params, draft_cfg = None, None
    if args.spec_k > 0:
        if not args.draft_arch:
            raise SystemExit("--spec-k needs --draft-arch")
        import dataclasses as _dc
        draft_cfg = _dc.replace(archs.get(args.draft_arch, smoke=args.smoke),
                                vocab_size=cfg.vocab_size, dtype=cfg.dtype)
        draft_params = T.init_params(jax.random.PRNGKey(2), draft_cfg)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size, jnp.int32)
    if args.shared_prompts > 0:
        s = min(args.shared_prompts, args.prompt_len)
        prompts = prompts.at[:, :s].set(prompts[0, :s])
    wave = args.wave or decode_wave(args.batch)
    sampler = SamplerConfig(max_new_tokens=args.new_tokens,
                            temperature=args.temperature,
                            eos_token=args.eos_token,
                            greedy=args.temperature <= 0)
    with mesh:
        gen = lambda spec=True, **kw: genserve.generate(
            params, cfg, prompts, jax.random.PRNGKey(1), sampler,
            wave=wave, fast_path=False, decode_chunk=args.decode_chunk,
            prefill_chunk=args.prefill_chunk, page_size=args.page_size,
            prefix_cache=args.prefix_cache,
            spec_k=args.spec_k if spec else 0,
            draft_params=draft_params if spec else None,
            draft_cfg=draft_cfg if spec else None, **kw)
        gen()            # warm-up: compile the engine programs
        t0 = time.time()
        ro, stats = gen()   # timed run is uninstrumented (TTFT stamping
        jax.block_until_ready(ro["sequences"])   # syncs admission)
        dt = time.time() - t0
        ro_ref = None
        if args.check_spec_parity and args.spec_k > 0:
            ro_ref, _ = gen(spec=False)
        obs_metrics.reset()   # quantiles below describe this run only
        _, ttft_stats = gen(measure_ttft=True)
    valid = float(jnp.sum(ro["mask"]))
    hit = float(stats.get("prefix_hit_rate", 0.0))
    rounds = prefill_rounds(args.prompt_len, args.prefill_chunk,
                            prefix_hit_rate=hit)
    ideal = predicted_occupancy(args.batch, wave=wave,
                                prefill_rounds=rounds,
                                max_new_tokens=args.new_tokens)
    p50, p95 = ttft_quantiles(ttft_stats)
    admission = (f"chunked (C={args.prefill_chunk})"
                 if args.prefill_chunk else "one-shot")
    if args.page_size:
        admission += f" paged (page={args.page_size})"
    if args.prefix_cache:
        admission += " +prefix-cache"
    print(f"arch={cfg.name} engine={stats['engine']} wave={stats['wave']} "
          f"batch={args.batch} admission={admission}")
    print(f"generated {ro['gen_tokens'].shape} in {dt:.2f}s "
          f"({valid / dt:.1f} valid tok/s; {stats['decode_steps']} decode "
          f"rounds, {stats['prefills']} prefill injections, "
          f"{stats.get('prefill_rounds', 0)} prefill-chunk rounds)")
    snap = obs_metrics.snapshot()
    ttft_h = snap.get("gen.ttft_s") or {}
    qw = snap.get("gen.queue_wait_s") or {}
    print(f"ttft p50={p50 * 1e3:.1f}ms p95={p95 * 1e3:.1f}ms "
          f"p99={ttft_h.get('p99', 0.0) * 1e3:.1f}ms")
    if qw.get("count"):
        print(f"queue wait p50={qw['p50'] * 1e3:.1f}ms "
              f"p95={qw['p95'] * 1e3:.1f}ms "
              f"p99={qw['p99'] * 1e3:.1f}ms "
              f"(n={qw['count']})")
    if args.prefix_cache:
        print(f"prefix cache: {hit:.1%} token hit rate "
              f"({stats['prefill_tokens_skipped']} of "
              f"{stats['prompt_tokens']} prompt tokens skipped)")
    if args.spec_k > 0:
        print(f"speculative: k={stats['spec_k']} "
              f"draft={draft_cfg.name} "
              f"accept rate {stats['accept_rate']:.1%} "
              f"({stats['spec_accepted']}/{stats['spec_proposed']} drafts; "
              f"{stats['spec_tokens']} tokens in {stats['decode_steps']} "
              f"verify rounds)")
    if args.prefill_chunk:
        print(f"busy wave occupancy (decode + prefill): "
              f"{stats['busy_occupancy']:.2f} "
              f"(cost-model ideal {ideal:.2f})")
    else:
        print(f"mean wave occupancy: {stats['mean_occupancy']:.2f} "
              f"(cost-model ideal {ideal:.2f})")
    print("sample:", ro["sequences"][0, :24].tolist())
    if args.trace:
        obs_trace.export_chrome(args.trace)
        print(f"trace -> {args.trace}")
    if args.metrics:
        obs_metrics.dump(args.metrics)
        print(f"metrics snapshot -> {args.metrics}")
    if args.expect_prefix_hits and hit <= 0.0:
        raise SystemExit("expected a nonzero prefix-cache hit rate "
                         f"(got {hit}) — shared-prompt trace not hitting")
    if ro_ref is not None:
        import numpy as _np
        same = _np.array_equal(
            _np.asarray(ro["gen_tokens"]) * _np.asarray(ro["mask"]),
            _np.asarray(ro_ref["gen_tokens"]) * _np.asarray(ro_ref["mask"]))
        if not same:
            raise SystemExit("speculative greedy decode diverged from the "
                             "non-speculative engine (token parity check)")
        print("spec parity: speculative == non-speculative (greedy)")
    return ro, stats


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    run_serve(args)


if __name__ == "__main__":
    main()
