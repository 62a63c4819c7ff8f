"""Training launcher.

Two modes:
  * ``--cluster-sim`` (default on CPU): the full elastic PRIME protocol
    with k stacked DiLoCo workers in one process — join/leave/crash
    schedules, int8 ring, bandwidth-aware reordering, checkpointing.
  * ``--distributed``: pjit/shard_map path against the production mesh
    (requires real or forced devices; the dry-run proves these programs
    compile for 256/512 chips).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
      --reduced --outer-steps 5 --inner-steps 10 --workers 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="intellect-1")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale sibling config")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--outer-steps", type=int, default=4)
    ap.add_argument("--inner-steps", type=int, default=None,
                    help="H (default: DiLoCo config, paper=100)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-per-worker", type=int, default=4)
    ap.add_argument("--quant", default="int8",
                    choices=["int8", "int4", "fp32"])
    ap.add_argument("--quant-impl", default="jnp",
                    choices=["jnp", "pallas"],
                    help="int8 ring codec: jnp reference or the Pallas "
                         "kernels (interpret mode off-TPU)")
    ap.add_argument("--overlap", default="none",
                    choices=["none", "delayed"],
                    help="none: outer sync is a barrier between inner "
                         "phases; delayed: the quantized ring runs "
                         "under the next inner phase (hops dispatched "
                         "between scan chunks) and the reduced pseudo-"
                         "gradient is applied one phase late (paper "
                         "§2.2 overlapped sync)")
    ap.add_argument("--inner-chunks", type=int, default=1,
                    help="jitted scan chunks per inner phase; the gaps "
                         "are where in-flight ring hops are dispatched "
                         "(>= ring hops + 1 hides the whole ring)")
    ap.add_argument("--sync-buckets", type=int, default=1,
                    help="sub-buckets per ring chunk-hop (independent "
                         "codebooks; pipelines compress/transmit)")
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--inner-lr", type=float, default=3e-4)
    ap.add_argument("--outer-lr", type=float, default=0.7)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-engine", default="flat",
                    choices=["flat", "store", "delta"],
                    help="flat npy dirs | content-addressed chunk "
                         "store | chunk store + int8/int4 delta chain")
    ap.add_argument("--ckpt-base-every", type=int, default=8,
                    help="delta engine: full re-anchor every N saves")
    ap.add_argument("--ckpt-codec", default="int8",
                    choices=["int8", "int4"])
    ap.add_argument("--serve-ckpt-port", type=int, default=None,
                    help="serve the chunk store to joiners on this "
                         "port after training (0 = ephemeral)")
    ap.add_argument("--join-from", default=None,
                    help="comma-separated host:port peers; swarm-fetch "
                         "the latest checkpoint into --ckpt-dir and "
                         "start from it")
    ap.add_argument("--join-mode", default="blocking",
                    choices=["blocking", "stream"],
                    help="blocking: fetch completes before step 0 (the "
                         "paper's production mode); stream: gossip + "
                         "background chunk streaming overlapped with "
                         "the inner phases, adopted at the first outer "
                         "boundary where the chain is fully assembled")
    ap.add_argument("--events", default=None,
                    help='JSON list like [[2,"join",5],[3,"crash",1]]')
    ap.add_argument("--seed", type=int, default=0)
    return ap


def build_trainer(args, sync_backend=None, *, n_layers: int | None = None,
                  max_workers: int | None = None):
    """The ElasticTrainer that ``args`` (from :func:`build_parser`)
    describe, with freshly initialised weights from ``args.seed``;
    ``sync_backend`` (``train.step.DistSyncBackend``) runs its outer
    sync as collectives over a device mesh; ``n_layers`` cuts the
    model's depth (widths unchanged); ``max_workers`` sets the stacked
    worker slots (default ``max(2 * workers, workers + 2)``,
    room for joiners)."""
    import jax

    from repro.configs import get_config
    from repro.core.diloco import DiLoCoConfig
    from repro.core.fault_tolerance import (ClusterSimulator, EventKind,
                                            NodeEvent)
    from repro.data.pipeline import DataConfig
    from repro.models.registry import get_model
    from repro.train.loop import ElasticTrainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(args.seed))

    events = []
    if args.events:
        for step, kind, nid in json.loads(args.events):
            events.append(NodeEvent(step, EventKind(kind), nid))
    sim = ClusterSimulator(list(range(args.workers)), events=events)

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                      batch_per_worker=args.batch_per_worker,
                      total_steps=args.outer_steps * (
                          args.inner_steps or 100))
    tcfg = TrainerConfig(
        diloco=DiLoCoConfig(
            inner_steps=args.inner_steps or 100, quant=args.quant,
            quant_impl=args.quant_impl, outer_lr=args.outer_lr,
            overlap=args.overlap,
            sync_buckets=args.sync_buckets,
            error_feedback=args.error_feedback),
        inner_lr=args.inner_lr, ckpt_dir=args.ckpt_dir,
        ckpt_engine=args.ckpt_engine,
        ckpt_delta_base_every=args.ckpt_base_every,
        ckpt_codec=args.ckpt_codec,
        inner_chunks=args.inner_chunks,
        max_workers=max_workers or max(args.workers * 2,
                                       args.workers + 2))
    return ElasticTrainer(model, tcfg, dcfg, params, sim,
                          sync_backend=sync_backend)


def main():
    args = build_parser().parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    trainer = build_trainer(args)
    tcfg = trainer.cfg

    if args.join_from:
        peers = []
        for hp in args.join_from.split(","):
            host, _, port = hp.rpartition(":")
            peers.append((host or "127.0.0.1", int(port)))
        assert args.ckpt_dir, "--join-from needs --ckpt-dir"
        assert args.ckpt_engine != "flat", \
            "--join-from fetches into a chunk store; use " \
            "--ckpt-engine store|delta"
        if args.join_mode == "stream":
            # overlapped onboarding: chunks stream + assemble in the
            # background while the inner phases run; the trainer
            # adopts at the first ready outer boundary
            trainer.begin_stream_join(peers)
            print(f"streaming join from {len(peers)} peers "
                  f"(gossip + background chunk streaming)")
        else:
            from repro.checkpointing import recover
            tree, meta, stats = recover(peers, args.ckpt_dir,
                                        trainer.checkpoint_like())
            trainer.adopt_checkpoint(tree, meta)
            print(f"joined via swarm: step {stats['step']}, "
                  f"{stats['chunks_fetched']} chunks "
                  f"({stats['bytes_fetched']} B) from "
                  f"{len(stats['per_peer'])} peers "
                  f"(reassigned={stats['reassigned_ranges']})")

    hist = trainer.run(args.outer_steps,
                       inner_steps=args.inner_steps)
    joins = [h["stream_join"] for h in hist if "stream_join" in h]
    for j in joins:
        st = j.get("stats", {})
        print(f"stream join: admitted={j['admitted']} "
              f"step={j.get('step')} "
              f"fetch={st.get('fetch_seconds', 0):.3f}s "
              f"chunks={st.get('chunks_fetched', 0)} "
              f"replayed_on_stream={st.get('replayed_on_stream', 0)}")
    if args.serve_ckpt_port is not None:
        assert args.ckpt_dir, "--serve-ckpt-port needs --ckpt-dir"
        if args.ckpt_engine == "flat":
            from repro.checkpointing import CheckpointServer
            peer = CheckpointServer(args.ckpt_dir,
                                    port=args.serve_ckpt_port)
            print(f"serving flat checkpoints on 127.0.0.1:{peer.port} "
                  f"(ctrl-C to stop)")
        else:
            peer = trainer.serve_checkpoints(port=args.serve_ckpt_port)
            print(f"serving chunk store on 127.0.0.1:{peer.port} "
                  f"(ctrl-C to stop)")
        try:
            import time
            while True:
                time.sleep(1)
        except KeyboardInterrupt:
            peer.close()
    for h in hist:
        print(json.dumps({k: v for k, v in h.items()
                          if k != "ring_order"}, default=str))
    if args.overlap == "delayed":
        led = trainer.comm_ledger
        falls = sum(1 for h in hist if "sync_fallback" in h)
        print(f"overlapped sync: {led.hidden_fraction:.0%} of ring "
              f"comm hidden under the chunked inner phase "
              f"({len(led.records)} windows, {falls} torn fallbacks)")
    print(f"final loss: {hist[-1]['loss']:.4f}  "
          f"bandwidth reduction vs fp32 DP: "
          f"{tcfg.diloco.inner_steps * 4 / (0.5 if args.quant=='int4' else (1 if args.quant=='int8' else 4)):.0f}x")


if __name__ == "__main__":
    main()
