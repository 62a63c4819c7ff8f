"""A DiLoCo training cell: ``ElasticTrainer`` with the benchmark's weights
and feed, driven one outer step per call of ``trainer.run(1)``.

Set-up builds the trainer and drives it through the checked outer steps
(which also compile every program the window runs); the window then
runs whole outer steps until ``--seconds`` have passed. After the
window the trainer is freed and the plain reference follows the checked
steps from the same weights and rows.
"""
from __future__ import annotations

import dataclasses
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, counts, harness, weights
from bench.reference import diloco as ref_diloco
from bench.reference import ssm_lm
from bench.traffic import diloco_train


def model_config(config: dict, rehearse: bool):
    """The registry's configuration with the file's vocabulary (the
    program pads its embedding to a multiple of 256 either way); every
    other number of the file has to be the program's."""
    from repro.configs import get_config
    cfg = get_config(config["registry_arch"])
    if rehearse:
        return dataclasses.replace(cfg.reduced(), **config.get(
            "rehearsal_overrides", {}))
    cfg = dataclasses.replace(cfg, vocab=int(config["model"]["vocab_size"]))
    harness.check_sizes(dict(config["model"], name=config["name"]),
                        program_sizes(cfg))
    return cfg


def program_sizes(cfg) -> dict:
    return dict(d_model=cfg.d_model, n_layer=cfg.n_layers,
                vocab_size=cfg.vocab, d_state=cfg.ssm.d_state,
                headdim=cfg.ssm.head_dim, ngroups=cfg.ssm.n_groups,
                d_conv=cfg.ssm.conv_kernel, expand=cfg.ssm.expand,
                norm_eps=cfg.norm_eps, z_loss_weight=cfg.max_z_weight,
                tie_embeddings=cfg.tie_embeddings)


def sizes(config: dict, cfg) -> dict:
    """The configuration's sizes as the program runs them (the
    rehearsal's are tiny)."""
    return dict(config["model"], **program_sizes(cfg))


class SteppedRing:
    """Outer-sync backend that stages the simulator ring as a steppable
    op (``RingSyncOp``: one jitted program per hop kind), the path the
    delayed overlap and ``chip_smoke.py`` take, here applied at once
    (synchronous DiLoCo). The trainer's default, the eager
    ``outer_sync_sim``, aborts the TPU compiler (PERF.md, section 7)."""

    def begin(self, *args, **kw):
        from repro.core import diloco
        return diloco.begin_outer_sync_sim(*args, **kw)


def build(cfg, traffic: dict, params, feed):
    from repro.core.diloco import DiLoCoConfig
    from repro.core.fault_tolerance import ClusterSimulator
    from repro.data.pipeline import DataConfig
    from repro.models.registry import get_model
    from repro.train.loop import ElasticTrainer, TrainerConfig

    k = int(traffic["workers"])
    h = int(traffic["inner_steps"])
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=int(traffic["seq_len"]),
                      batch_per_worker=int(traffic["batch_per_worker"]))
    tcfg = TrainerConfig(
        diloco=DiLoCoConfig(inner_steps=h, quant=traffic["quant"],
                            quant_impl=traffic["quant_impl"],
                            outer_lr=float(traffic["outer_lr"]),
                            outer_momentum=float(traffic["outer_momentum"]),
                            overlap=traffic["overlap"]),
        inner_lr=float(traffic["inner_lr"]), inner_chunks=1,
        max_workers=k)
    return ElasticTrainer(get_model(cfg), tcfg, dcfg, params,
                          ClusterSimulator(list(range(k))),
                          batch_provider=feed, sync_backend=SteppedRing())


def reference(config_sizes: dict, traffic: dict, shapes, init: dict,
              seed: int, n_steps: int, precision: str = "f32",
              fault: str | None = None, device=None) -> dict:
    """The plain reference over the first ``n_steps`` outer steps."""
    params0 = weights.make(shapes, init, seed, device)
    feed = diloco_train.Feed(traffic, seed, config_sizes["vocab_size"])

    def loss_fn(p, tokens, targets, mask):
        return ssm_lm.loss(config_sizes, p, tokens, targets, mask,
                           precision)

    opt = {"lr": float(traffic["inner_lr"]), **traffic["adamw"]}
    outer = {"lr": float(traffic["outer_lr"]),
             "momentum": float(traffic["outer_momentum"])}
    return ref_diloco.run(loss_fn, params0, feed,
                          k=int(traffic["workers"]),
                          h=int(traffic["inner_steps"]), n_steps=n_steps,
                          opt=opt, outer=outer, fault=fault)


def program_checked_steps(trainer, params0, n_steps: int) -> dict:
    """Drive ``trainer`` through its first ``n_steps`` outer steps with
    its own call and feed, and read what the comparison needs: the loss
    of every inner step (kept from the inner-phase program's own
    output), the outer momentum after step 1 and the anchor's change."""
    h = trainer.cfg.diloco.inner_steps
    inner, seen = trainer.inner_phase_jit, []

    def recording(*args):
        out = inner(*args)
        seen.append(out[2])
        return out

    trainer.inner_phase_jit = recording
    trainer.run(1, inner_steps=h)
    grad = ref_diloco.leaf_norms(trainer.outer.opt.momentum)
    if n_steps > 1:
        trainer.run(n_steps - 1, inner_steps=h)
    trainer.inner_phase_jit = inner
    change = ref_diloco.leaf_norms(jax.tree.map(
        lambda a, p: a - p.astype(jnp.float32), trainer.outer.anchor,
        params0))
    return {"step_losses": [np.asarray(x, np.float64).T.tolist()
                            for x in seen],
            "grad_norms": grad, "change_norms": change}


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        clock: harness.CompileClock, t_start: float, devs,
        rehearse: bool = False) -> dict:
    from repro.models.registry import get_model

    traffic = dict(cell.traffic)
    if rehearse:
        traffic.update(traffic.get("rehearsal", {}))
    cfg = model_config(cell.config, rehearse)
    csz = sizes(cell.config, cfg)
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda key: model.init(key)[0],
                            jax.random.PRNGKey(0))
    n_params = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    init = cell.config["init"]
    k, h = int(traffic["workers"]), int(traffic["inner_steps"])
    n_check = int(traffic["check_steps"])
    tokens_per_step = k * h * int(traffic["batch_per_worker"]) \
        * int(traffic["seq_len"])

    params0 = weights.make(shapes, init, seed, devs[0])
    feed = diloco_train.Feed(traffic, seed, cfg.vocab)
    trainer = build(cfg, traffic, params0, feed)
    prog = program_checked_steps(trainer, params0, n_check)
    del params0
    jax.block_until_ready(trainer.outer.anchor_flat)
    setup_s = harness.now() - t_start
    compile_s, _ = clock.mark()
    harness.info(f"setup {setup_s:.3f} s (compile/cache {compile_s:.3f} s),"
                 f" {n_check} checked outer steps")

    # -- the window: whole outer steps until --seconds have passed ----------
    _, c0 = clock.mark()
    step_walls, losses = [], []
    trace_dir = harness.TRACE_DIR / f"{cell.name}-{seed}"
    if trace:
        jax.profiler.start_trace(str(trace_dir))
    t0 = harness.now()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            s0 = harness.now()
            with jax.profiler.TraceAnnotation("bench.outer_step"):
                rec = trainer.run(1, inner_steps=h)[-1]
                jax.block_until_ready(trainer.outer.anchor_flat)
            t1 = harness.now()
            step_walls.append(t1 - s0)
            losses.append(float(rec["loss"]))
            if t1 - t0 >= seconds:
                break
    window_s = t1 - t0
    if trace:
        jax.profiler.stop_trace()
    _, c1 = clock.mark()
    harness.info(f"window {window_s:.3f} s, {len(step_walls)} outer steps,"
                 f" programs compiled or read from the cache inside it: {c1 - c0}")
    device = harness.device_record(devs)
    del trainer, feed
    gc.collect()

    steps = len(step_walls)
    failed = sum(1 for x in losses if not math.isfinite(x))
    rate = steps * tokens_per_step / window_s
    rec = {"setup": {"compile_s": compile_s},
           "train": {"window_s": window_s, "steps": steps,
                     "step_walls_s": step_walls,
                     "tokens_per_s": rate,
                     "flops_per_token":
                         counts.ssm_train_flops_per_token(csz),
                     "codec_bytes_per_step":
                         counts.int8_ring_codec_bytes(n_params, k)},
           "chips": len(devs)}

    # -- correctness: the reference follows the checked steps ---------------
    ref = reference(csz, traffic, shapes, init, seed, n_check,
                    device=devs[0])
    numbers = compare.train_numbers(prog, ref)
    checks = compare.checks(numbers, traffic["limits"])
    return {"rec": rec, "trace_dir": trace_dir if trace else None,
            "metrics": {"setup_s": setup_s, "train_tokens_per_s": rate},
            "attempted": steps, "failed": failed, "device": device,
            "checks": checks, "numbers": numbers,
            "detail": {"leaves": leaf_names(shapes), "program": prog,
                       "reference": ref}}


def leaf_names(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def control_readings(cell: harness.Cell, seed: int, devs,
                     rehearse: bool = False,
                     variants=(("fp8", None), ("f32", "half_batch"),
                               ("f32", "no_exchange"))) -> dict:
    """The comparison's numbers for the control (the reference at fp8 in
    the program's place) and for each planted fault, at the cell's own
    size: each variant's numbers against the float32 reference."""
    from repro.models.registry import get_model
    traffic = dict(cell.traffic)
    if rehearse:
        traffic.update(traffic.get("rehearsal", {}))
    cfg = model_config(cell.config, rehearse)
    csz = sizes(cell.config, cfg)
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda key: model.init(key)[0],
                            jax.random.PRNGKey(0))
    n = int(traffic["check_steps"])
    base = reference(csz, traffic, shapes, cell.config["init"], seed, n,
                     device=devs[0])
    out = {}
    for prec, fault in variants:
        got = reference(csz, traffic, shapes, cell.config["init"], seed, n,
                        precision=prec, fault=fault, device=devs[0])
        out[fault or prec] = dict(compare.train_numbers(got, base),
                                  detail={"variant": got, "reference": base})
    out["unchanged"] = {"change_gap": 1.0}
    return out
