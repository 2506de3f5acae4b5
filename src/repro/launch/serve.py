"""Serving driver: LM decode loop, plus the ANN retrieval tier.

LM mode — batched prefill + decode loop with KV caches:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --smoke \
        --batch 4 --prompt-len 16 --gen 16

ANN mode (``--ann``) — RAG retrieval through the service layer: stands
up :class:`repro.service.AnnService` from CLI knobs (engine kind,
replicas, router policy, LUT cache) or — the deploy path — from a
durable spec file (``--spec deploy.json``, the same artifact
``python -m repro.service --spec`` boots, so the two entrypoints can
never drift), streams a Zipf-skewed query trace through the replica
fleet (``--clock wall`` drives the executor-backed async path), and
prints the aggregate latency/hit-rate stats.  With ``--arch`` as well,
the retrieved document vectors feed the LM decode loop as
cross-attention context (the full RAG path):

    PYTHONPATH=src python -m repro.launch.serve --ann --replicas 2 \
        --router cache_aware --requests 64
    PYTHONPATH=src python -m repro.launch.serve --ann --spec deploy.json \
        --clock wall --requests 64
    PYTHONPATH=src python -m repro.launch.serve --ann --autotune \
        --slo-recall 0.8 --slo-p99-ms 50 --requests 64
    PYTHONPATH=src python -m repro.launch.serve --ann \
        --arch llama32_vision_11b --smoke --gen 8

``--autotune`` replaces the hand-picked CLI knobs with the SLO-driven
auto-tuner (``core.autotune``): the spec is *derived* — searched
against the perf model and validated on a calibration stream — then
the same fleet is stood up and streamed as usual.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.models import (init_params, forward, encode, init_caches,
                          decode_step)
from repro.util import enable_compile_cache


def generate(cfg, params, prompts: jax.Array, gen_len: int,
             ctx: jax.Array | None = None, temperature: float = 0.0,
             seed: int = 0):
    """Greedy (or sampled) continuation of (B, P) prompt tokens.

    Prefill is run via forward (teacher-forced cache build happens inside
    the decode loop for simplicity at smoke scale: the prompt is replayed
    token-by-token, which exercises exactly the serve_step the dry-run
    lowers)."""
    b, plen = prompts.shape
    max_len = plen + gen_len
    enc_out = encode(params, cfg, ctx) if cfg.is_encdec else None
    caches = init_caches(cfg, batch=b, max_len=max_len)
    step = jax.jit(lambda p, t, pos, c: decode_step(
        p, cfg, t, pos, c, ctx=None if cfg.is_encdec else ctx,
        enc_out=enc_out))
    key = jax.random.PRNGKey(seed)
    tok = prompts[:, :1]
    out = [prompts]
    logits = None
    for t in range(max_len - 1):
        logits, caches = step(params, tok, jnp.full((b,), t, jnp.int32),
                              caches)
        logits = logits[:, -1, :]                  # (B, 1, V) -> (B, V)
        if t + 1 < plen:
            tok = prompts[:, t + 1:t + 2]          # teacher-forced prefill
        else:
            if temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits / temperature,
                                             axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            tok = nxt[:, None].astype(prompts.dtype)
            out.append(tok)
    return jnp.concatenate(out, axis=1)


def serve_ann(args):
    """RAG retrieval mode: AnnService over a synthetic document corpus,
    optionally feeding the LM decode loop."""
    from repro.data import make_clustered_corpus
    from repro.service import AnnService, IndexSpec, ServiceSpec

    d_embed = 32
    ds = make_clustered_corpus(seed=0, n=10_000, d=d_embed,
                               n_queries=max(args.batch, 32),
                               n_components=16)
    if args.autotune:
        # derive the spec instead of hand-picking it: perf-model
        # shortlist -> measured calibration -> SLO-validated ServiceSpec
        # (k stays at the tuner's slo.k; retrieval depth is sliced below)
        from repro.service import (SLO, SLOInfeasible, TuneSpace,
                                   autotune_service)
        slo = SLO(recall_at_k=args.slo_recall, p99_ms=args.slo_p99_ms)
        # m carries recall on this d=32 corpus (m=8 caps near 0.59);
        # nprobe past 8 of the 32 lists buys nothing but latency
        space = TuneSpace(m=(8, 16), nprobe=(4, 8),
                          lut_dtype=("uint8", "f32"),
                          buckets=((1, 2, 4),), tasks_per_shard=(256,),
                          cache_capacity_bytes=(0, 1 << 19))
        try:
            svc, res = autotune_service(
                np.asarray(ds.points), slo,
                queries=np.asarray(ds.queries, np.float32),
                space=space, nlist=32, replicas=args.replicas,
                router=args.router, seed=0)
        except SLOInfeasible as e:
            print(f"[ann] INFEASIBLE: {e}")
            for entry in e.frontier:
                print(f"[ann]   m={entry['m']} nprobe={entry['nprobe']} "
                      f"lut={entry['lut_dtype']}: "
                      f"recall={entry['recall']:.3f} "
                      f"p99={entry['p99_ms']:.2f}ms")
            raise SystemExit(1)
        for line in res.report().splitlines():
            print(f"[ann] {line}")
        spec = res.spec
    elif args.spec:
        # the durable deploy artifact: identical fleet to
        # `python -m repro.service --spec` (index is rebuilt per
        # spec.index over this corpus; k is forced to the RAG depth)
        import dataclasses as _dc
        spec = _dc.replace(ServiceSpec.load(args.spec), k=4)
    else:
        spec = ServiceSpec(
            engine=args.engine, replicas=args.replicas, router=args.router,
            nprobe=8, k=4, strategy="gather",
            index=IndexSpec(nlist=32, m=8, cb=64),
            n_shards=4, tasks_per_shard=256,
            buckets=(1, 2, 4), max_wait_s=1e-3,
            cache_capacity=args.cache_capacity)
    if not args.autotune:
        svc = AnnService.build(spec, points=ds.points,
                               sample_queries=ds.queries)
        svc.warmup()

    # Zipf-skewed arrivals over the query pool (hot queries repeat —
    # what the LUT cache and the cache-aware router are for)
    from repro.data import make_query_stream
    queries = np.asarray(ds.queries, np.float32)
    reqs = svc.stream(make_query_stream(queries, args.requests, args.qps,
                                        skew=1.2), clock=args.clock)
    st = svc.stats()
    agg, rt = st["aggregate"], st["router"]
    print(f"[ann] {agg['requests']} requests over {svc.n_replicas} "
          f"replica(s), router={rt['policy']} picks={rt['picks']}")
    print(f"[ann] p50={agg['p50_ms']:.2f}ms p99={agg['p99_ms']:.2f}ms "
          f"qps={agg['qps']:.0f} "
          f"lut_hit_rate={agg.get('lut_hit_rate', 0.0):.2f}")

    if args.arch is None:
        svc.shutdown()
        return
    # -- feed retrieved docs into the LM as context embeddings ------------
    cfg = registry.get_config(args.arch, smoke=args.smoke)
    if not (cfg.is_encdec or "cross_attn" in cfg.layer_types):
        raise SystemExit(
            f"--ann --arch {args.arch}: this arch has no cross-attention/"
            f"encoder path, so the retrieved context would be silently "
            f"ignored; pick e.g. llama32_vision_11b or whisper_base")
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    doc_ids = np.stack([r.ids for r in reqs[:args.batch]])
    retrieved = np.asarray(ds.points)[np.maximum(doc_ids, 0)]   # (B, k, d)
    proj = np.random.default_rng(0).normal(
        0, 0.02, size=(d_embed, cfg.d_model))
    ctx = jnp.asarray(retrieved.astype(np.float32) @ proj)
    ctx_len = cfg.vision_ctx if "cross_attn" in cfg.layer_types \
        else cfg.encoder_ctx
    ctx = jnp.pad(ctx, ((0, 0), (0, ctx_len - ctx.shape[1]), (0, 0)))
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (doc_ids.shape[0], args.prompt_len), 0,
                                 cfg.vocab_size)
    toks = generate(cfg, params, prompts, args.gen, ctx=ctx)
    print(f"[ann] RAG decode over retrieved context: generated "
          f"{toks.shape} tokens")
    svc.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    # -- ANN retrieval mode (service layer) -------------------------------
    ap.add_argument("--ann", action="store_true",
                    help="RAG retrieval via repro.service.AnnService")
    ap.add_argument("--engine", default="local",
                    choices=("local", "sharded"))
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--router", default="cache_aware",
                    choices=("round_robin", "least_queue", "cache_aware"))
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--qps", type=float, default=2000.0)
    ap.add_argument("--cache-capacity", type=int, default=2048)
    ap.add_argument("--spec", metavar="PATH",
                    help="boot the fleet from a ServiceSpec deploy file "
                         "(.json/.yaml) instead of the CLI knobs above")
    ap.add_argument("--autotune", action="store_true",
                    help="derive the spec with the SLO-driven auto-tuner "
                         "(core.autotune) instead of CLI knobs / --spec")
    ap.add_argument("--slo-recall", type=float, default=0.8,
                    help="--autotune: required recall@k (default 0.8)")
    ap.add_argument("--slo-p99-ms", type=float, default=50.0,
                    help="--autotune: paced p99 budget in ms (default 50)")
    ap.add_argument("--clock", choices=("virtual", "wall"),
                    default="virtual",
                    help="stream driver: discrete-event simulation or "
                         "wall-clock executor-backed replicas")
    args = ap.parse_args()
    enable_compile_cache()
    if args.ann:
        serve_ann(args)
        return
    if args.arch is None:
        ap.error("--arch is required unless --ann is given")
    cfg = registry.get_config(args.arch, smoke=args.smoke)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    ctx = None
    if cfg.is_encdec:
        ctx = jax.random.normal(key, (args.batch, cfg.encoder_ctx,
                                      cfg.d_model), jnp.float32)
    elif "cross_attn" in cfg.layer_types:
        ctx = jax.random.normal(key, (args.batch, cfg.vision_ctx,
                                      cfg.d_model), jnp.float32)
    t0 = time.time()
    toks = generate(cfg, params, prompts, args.gen, ctx=ctx)
    dt = time.time() - t0
    n_new = args.batch * args.gen
    print(f"[serve] generated {toks.shape} in {dt:.1f}s "
          f"({n_new / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
