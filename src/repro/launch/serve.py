"""Serving launcher: batched generation with the wave or continuous
engine, with tokens/sec and request-latency percentiles at exit.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m \
      --reduced --requests 8 --max-new 16 --engine continuous

``--engine wave`` keeps the legacy static batcher for A/B runs;
``--attn-impl pallas`` routes decode attention through the Pallas
flash-decode kernel (interpret mode off-TPU).

``--swarm`` demos fault-tolerant swarm inference instead: it brings up
an in-process fleet of ``--stages x --replicas`` StageServers (weight
distribution via the chunk swarm), routes the same requests through a
``SwarmRouter``, crashes one stage holder mid-run, and checks the
emitted tokens stay bit-identical to the single-host engine:

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
      --reduced --swarm --stages 2 --replicas 2 --requests 4
"""
from __future__ import annotations

import argparse
import dataclasses


def _run_swarm(args, cfg, model, params):
    import tempfile
    import time
    from pathlib import Path

    import numpy as np

    from repro.checkpointing import (ChunkGossip, ChunkPeer, ChunkStore,
                                     PeerConnPool)
    from repro.serving import (StageServer, SwarmRouter, publish_stages)
    from repro.serving.engine import ContinuousEngine, Request
    from repro.models import registry

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(2, cfg.vocab,
                            size=max(1, int(rng.integers(
                                args.prompt_len // 2,
                                args.prompt_len + 1)))).tolist()
               for _ in range(args.requests)]

    # single-host greedy reference
    engine = ContinuousEngine(model, params, batch_slots=args.slots,
                              max_len=args.max_len)
    reqs = [Request(rid=i, prompt=np.asarray(p, np.int32),
                    max_new_tokens=args.max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained()
    reference = [list(r.out_tokens) for r in reqs]

    stages = registry.make_stages(cfg, args.stages)
    servers, pool, gossip = {}, None, None
    with tempfile.TemporaryDirectory() as td:
        root = Path(td)
        seed_store = ChunkStore(root / "seed")
        publish_stages(seed_store, cfg, params, args.stages)
        seed_peer = ChunkPeer(seed_store)
        try:
            for sid in range(args.stages):
                sp = stages[sid].slice_params(params)
                for r in range(args.replicas):
                    srv = StageServer(
                        cfg, ChunkStore(root / f"srv_{sid}_{r}"),
                        k_stages=args.stages, max_len=args.max_len)
                    srv.serve_stage(sid, sp)
                    servers[(sid, r)] = srv
            pool = PeerConnPool(timeout=args.timeout)
            gossip = ChunkGossip([s.addr for s in servers.values()],
                                 timeout=args.timeout, pool=pool)
            gossip.poll_once()
            router = SwarmRouter(args.stages, gossip,
                                 timeout=args.timeout, pool=pool,
                                 max_len=args.max_len)
            if args.replicas > 1 and args.requests > 1:
                # crash a mid-chain holder a few responses into the
                # run: the router must fail over and re-prefill
                victim = servers[(args.stages // 2, 0)]
                victim.crash_after = victim.served_chunks + 3
            t0 = time.perf_counter()
            outs = [router.generate(p, args.max_new, rid=f"req{i}",
                                    eos_id=engine.eos_id)
                    for i, p in enumerate(prompts)]
            wall = time.perf_counter() - t0
            st = router.stats
            ntok = sum(len(o) for o in outs)
            identical = outs == reference
            print(f"swarm stages={args.stages} replicas={args.replicas} "
                  f"requests={len(outs)} tokens={ntok} "
                  f"tok/s={ntok / max(wall, 1e-9):.1f}")
            print(f"failovers={st['failovers']} "
                  f"recoveries={st['recoveries']} "
                  f"replayed_tokens={st['replayed_tokens']} "
                  f"recovery_s={st['recovery_s']:.3f} "
                  f"pool_reused={pool.stats['reused']}")
            print(f"bit_identical_to_engine={identical}")
            if not identical:
                raise SystemExit("swarm outputs diverged from engine")
        finally:
            if gossip is not None:
                gossip.stop()
            if pool is not None:
                pool.close()
            for s in servers.values():
                s.close()
            seed_peer.close()


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=["wave", "continuous", "paged"])
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged engine: KV cells per physical block")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="paged engine: physical pool size (default "
                         "matches the dense per-slot budget)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling threshold (0 = off)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--attn-impl", default="jnp",
                    choices=["jnp", "pallas"])
    ap.add_argument("--no-bucket", action="store_true",
                    help="disable power-of-two prompt pad bucketing")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--swarm", action="store_true",
                    help="fault-tolerant swarm-inference demo")
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=5.0)
    return ap


def build_model(args):
    """(cfg, model, params) for ``args`` (from :func:`build_parser`):
    the configured architecture with its decode-attention backend and
    freshly initialised weights from ``args.seed``."""
    import jax

    from repro.configs import get_config
    from repro.models.registry import get_model

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.attn_impl != cfg.decode_attn_impl:
        cfg = dataclasses.replace(cfg, decode_attn_impl=args.attn_impl)
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(args.seed))
    return cfg, model, params


def build_engine(args, model, params, *, batch_admit: bool = True):
    """The ``args.engine`` serving engine over ``model``/``params``.
    ``batch_admit=False`` prefills admissions one request at a time, as
    the paged engine always does."""
    from repro.serving.engine import make_engine

    engine_kw = dict(batch_slots=args.slots, max_len=args.max_len,
                     bucket_prompts=not args.no_bucket,
                     decode_chunk=args.decode_chunk,
                     top_k=args.top_k, top_p=args.top_p,
                     seed=args.seed, batch_admit=batch_admit)
    if args.engine == "paged":
        engine_kw.update(block_size=args.block_size,
                         pool_blocks=args.pool_blocks)
    return make_engine(args.engine, model, params, **engine_kw)


def submit_requests(engine, args, vocab: int) -> list:
    """Queue ``args.requests`` seeded random prompts; returns them."""
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        plen = max(1, int(rng.integers(args.prompt_len // 2,
                                       args.prompt_len + 1)))
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(2, vocab, size=plen).astype(np.int32),
            max_new_tokens=args.max_new,
            temperature=args.temperature))
        engine.submit(reqs[-1])
    return reqs


def main():
    ap = build_parser()
    args = ap.parse_args()
    if args.engine == "wave" and args.temperature > 0:
        ap.error("--engine wave is greedy-only; use --engine "
                 "continuous for --temperature > 0")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg, model, params = build_model(args)
    if args.swarm:
        _run_swarm(args, cfg, model, params)
        return
    engine = build_engine(args, model, params)
    submit_requests(engine, args, cfg.vocab)
    engine.run_until_drained()
    s = engine.perf_summary()
    print(f"engine={s['engine']} requests={s['requests']} "
          f"tokens={s['tokens_out']} decode_steps={s['decode_steps']}")
    print(f"tok/s={s['tokens_per_s']:.1f} "
          f"p50_latency={s['latency_p50_s'] * 1e3:.1f}ms "
          f"p95_latency={s['latency_p95_s'] * 1e3:.1f}ms "
          f"occupancy={s['slot_occupancy']:.2f} "
          f"host_syncs={s['host_syncs']} "
          f"prefill_widths={s['prefill_widths']}")
    if args.engine == "paged":
        print(f"block_size={s['block_size']} "
              f"blocks_peak={s['blocks_peak']}/{s['pool_blocks']} "
              f"prefix_hit_rate={s['prefix_hit_rate']:.2f} "
              f"cow_forks={s['cow_forks']} "
              f"paged_extends={s['paged_extends']}")


if __name__ == "__main__":
    main()
