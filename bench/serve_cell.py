"""A serving cell: ``ContinuousEngine`` with the benchmark's weights,
driven by closed-loop clients, one ``engine.step()`` per loop turn.

Set-up makes the weights, builds the engine, warms every prefill width
the traffic reaches plus the admit step and the decode chunk, then runs
the clients for ``preroll_s`` so the window starts in steady state. The
window runs until ``--seconds`` have passed. After it the engine is
freed and the plain reference reads a seeded sample of the finished
greedy requests (the longest among them).
"""
from __future__ import annotations

import dataclasses
import gc
import re

import jax
import numpy as np

from bench import compare, counts, harness, weights
from bench.reference import dense_lm
from bench.traffic import closed_loop_serve

NEVER = -1     # an end-of-sequence id no token can take


def model_config(config: dict, traffic: dict, rehearse: bool):
    """The registry's configuration with the file's vocabulary (the
    program pads its embedding to a multiple of 256 either way); every
    other number of the file has to be the program's."""
    from repro.configs import get_config
    cfg = get_config(config["registry_arch"])
    if rehearse:
        cfg = dataclasses.replace(cfg.reduced(), **config.get(
            "rehearsal_overrides", {}))
    else:
        cfg = dataclasses.replace(
            cfg, vocab=int(config["model"]["vocab_size"]))
        harness.check_sizes(dict(config["model"], name=config["name"]),
                            program_sizes(cfg))
    return dataclasses.replace(cfg, decode_attn_impl=traffic["attn_impl"])


def program_sizes(cfg) -> dict:
    return dict(hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
                num_attention_heads=cfg.n_heads,
                num_key_value_heads=cfg.n_kv_heads,
                intermediate_size=cfg.d_ff, vocab_size=cfg.vocab,
                rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
                tie_word_embeddings=cfg.tie_embeddings)


def sizes(config: dict, cfg) -> dict:
    return dict(config["model"], **program_sizes(cfg))


def prefill_width(n: int, max_len: int) -> int:
    from repro.serving.engine import bucket_len
    return max(min(bucket_len(n), max_len), n)


class Clients:
    """Closed-loop clients: each keeps one request in the engine and
    sends the next as soon as it has finished."""

    def __init__(self, engine, stream, n: int):
        self.engine, self.stream = engine, stream
        self.slots = [None] * n
        self.finished = []
        self.rid = 0

    def refill(self) -> None:
        from repro.serving.engine import Request
        for i, r in enumerate(self.slots):
            if r is not None and not r.done:
                continue
            if r is not None:
                self.finished.append(r)
            prompt, spec = self.stream.next()
            req = Request(rid=self.rid, prompt=prompt,
                          max_new_tokens=spec.max_new,
                          temperature=spec.temperature)
            self.rid += 1
            self.engine.submit(req)
            self.slots[i] = req


def warm(engine, traffic: dict, vocab: int) -> list[int]:
    """Compile every prefill width the traffic's request set reaches,
    the admit step and the decode chunk, with one short request each."""
    from repro.serving.engine import Request
    specs = closed_loop_serve.request_set(traffic)
    by_width = {}
    for s in specs:
        by_width.setdefault(prefill_width(s.prompt_len, engine.max_len),
                            s.prompt_len)
    for i, (w, plen) in enumerate(sorted(by_width.items())):
        engine.submit(Request(rid=-1 - i,
                              prompt=np.full((plen,), 2, np.int32),
                              max_new_tokens=2 * engine.decode_chunk + 1,
                              temperature=float(traffic["temperature"])))
        engine.run_until_drained()
    engine.reset_metrics()
    return sorted(by_width)


def sample(finished, n: int, seed: int) -> list:
    """The longest finished greedy request and ``n - 1`` more drawn from
    the seed."""
    greedy = [r for r in finished if r.temperature == 0.0]
    if not greedy:
        return []
    longest = max(greedy, key=lambda r: len(r.out_tokens))
    rest = [r for r in greedy if r is not longest]
    rng = harness.seed_rng(seed, 0xC4EC)
    pick = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(csz: dict, shapes, init: dict, seed: int, seqs,
                   n_pos: int, precisions=("f32",), device=None,
                   bucket: int = 1024) -> dict:
    """For each sampled (prompt, served tokens): the widest gap of the
    served tokens under the float32 reference, and for each other
    precision the widest gap of the token it puts first. Rows are padded
    to ``bucket`` multiples and positions to ``n_pos`` (the longest
    output), so each row length compiles once."""
    params = weights.make(shapes, init, seed, device)
    fns = {p: jax.jit(lambda prm, t, pos, p=p: dense_lm.logits_at(
        csz, prm, t, pos, p)) for p in set(precisions) | {"f32"}}
    worst = {p: 0.0 for p in precisions}
    for prompt, out in seqs:
        row = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        n = -(-len(row) // bucket) * bucket
        toks = np.zeros((n,), np.int32)
        toks[:len(row)] = row
        k = len(out)
        pos = np.full((max(n_pos, k),), len(row) - 1, np.int32)
        pos[:k] = np.arange(len(prompt) - 1, len(row))
        ref = np.asarray(fns["f32"](params, toks, pos), np.float64)[:k]
        for p in precisions:
            if p == "f32":
                got = np.asarray(out, np.int64)
            else:
                got = np.asarray(fns[p](params, toks, pos))[:k].argmax(-1)
            worst[p] = max(worst[p], compare.logit_gap(ref, got))
    return worst


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        clock: harness.CompileClock, t_start: float, devs,
        rehearse: bool = False, precisions=("f32",)) -> dict:
    from repro.models.registry import get_model
    from repro.serving.engine import make_engine

    traffic = dict(cell.traffic)
    if rehearse:
        traffic.update(traffic.get("rehearsal", {}))
    cfg = model_config(cell.config, traffic, rehearse)
    csz = sizes(cell.config, cfg)
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda key: model.init(key)[0],
                            jax.random.PRNGKey(0))
    init = cell.config["init"]
    params = weights.make(shapes, init, seed, devs[0])
    engine = make_engine(
        traffic["engine"], model, params, batch_slots=int(traffic["slots"]),
        max_len=int(traffic["max_len"]), bucket_prompts=True,
        decode_chunk=int(traffic["decode_chunk"]), top_k=0, top_p=0.0,
        seed=int(seed) & 0x7FFFFFFF,
        batch_admit=False,
        capture_logprobs=bool(traffic["capture_logprobs"]), eos_id=NEVER)
    widths = warm(engine, traffic, cfg.vocab)
    stream = closed_loop_serve.Stream(traffic, seed, cfg.vocab)
    clients = Clients(engine, stream, int(traffic["clients"]))
    t_pre = harness.now()
    while harness.now() - t_pre < float(traffic["preroll_s"]):
        clients.refill()
        engine.step()
    setup_s = harness.now() - t_start
    compile_s, _ = clock.mark()
    harness.info(f"setup {setup_s:.3f} s (compile/cache {compile_s:.3f} s),"
                 f" prefill widths {widths}")

    # -- the window ---------------------------------------------------------
    _, c0 = clock.mark()
    tok0, pre0 = engine.stats["tokens_out"], engine.stats["prefills"]
    out0 = {r.rid: len(r.out_tokens) for r in clients.slots if r}
    trace_dir = harness.TRACE_DIR / f"{cell.name}-{seed}"
    if trace:
        jax.profiler.start_trace(str(trace_dir))
    t0 = harness.now()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.clients"):
                clients.refill()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                engine.step()
            t1 = harness.now()
            if t1 - t0 >= seconds:
                break
    window_s = t1 - t0
    if trace:
        jax.profiler.stop_trace()
    _, c1 = clock.mark()
    tokens = engine.stats["tokens_out"] - tok0
    prefills = engine.stats["prefills"] - pre0
    device = harness.device_record(devs)
    everyone = clients.finished + [r for r in clients.slots if r]
    out1 = {r.rid: len(r.out_tokens) for r in everyone}
    harness.info(f"window {window_s:.3f} s, {tokens} tokens, {prefills} "
                 f"prefills, programs compiled or read from the cache inside it: {c1 - c0}")
    clients.engine = None
    del engine, params
    gc.collect()

    in_w = lambda t: t is not None and t0 <= t <= t1  # noqa: E731
    ttft = [r.t_first - r.t_submit for r in everyone if in_w(r.t_first)]
    tpot = [(r.t_done - r.t_first) / (len(r.out_tokens) - 1)
            for r in everyone if in_w(r.t_done) and len(r.out_tokens) > 1]
    done_in = [r for r in everyone if in_w(r.t_done)]
    metrics = {"setup_s": setup_s, "serve_tokens_per_s": tokens / window_s}
    lat = {"ttft": ttft, "tpot": tpot}
    for m in cell.end_to_end:       # ttft_p95_ms, tpot_p90_ms, ...
        mt = re.fullmatch(r"(ttft|tpot)_p(\d+)_ms", m["name"])
        if mt and lat[mt[1]]:
            metrics[m["name"]] = 1e3 * float(
                np.percentile(lat[mt[1]], int(mt[2])))
    for k, v in lat.items():
        if v:
            harness.info(f"{k}: {len(v)} requests, ms p50/p90/p95 "
                         f"{[round(1e3 * float(np.percentile(v, q)), 3) for q in (50, 90, 95)]}")

    # work counted from shapes: the prefills whose first token came in
    # the window, and every token decoded in it (output token j >= 1 of
    # a request decodes at context prompt + j)
    flops, fd_bytes = 0.0, 0
    for r in everyone:
        if in_w(r.t_first):
            flops += counts.dense_prefill_flops(csz, len(r.prompt))
        for j in range(max(1, out0.get(r.rid, 0)), out1[r.rid]):
            ctx = len(r.prompt) + j
            flops += counts.dense_decode_flops(csz, ctx)
            fd_bytes += counts.flash_decode_bytes(csz, ctx)
    rec = {"setup": {"compile_s": compile_s},
           "serve": {"window_s": window_s, "tokens": tokens,
                     "prefills": prefills, "model_flops": flops,
                     "flash_decode_bytes": fd_bytes},
           "chips": len(devs)}

    # -- correctness --------------------------------------------------------
    picked = sample(done_in, int(traffic["check_sample"]), seed)
    seqs = [(np.asarray(r.prompt), np.asarray(r.out_tokens, np.int64))
            for r in picked]
    numbers = {"logit_gap": float("nan")}
    if seqs:
        gaps = reference_gaps(csz, shapes, init, seed, seqs,
                              int(traffic["output_len"][1]),
                              precisions=precisions, device=devs[0])
        numbers["logit_gap"] = gaps["f32"]
        numbers.update({f"logit_gap.{p}": g for p, g in gaps.items()
                        if p != "f32"})
    checks = compare.checks(numbers, traffic["limits"])
    return {"rec": rec, "trace_dir": trace_dir if trace else None,
            "metrics": metrics, "failed": 0,
            "attempted": sum(1 for r in everyone
                             if r.t_done is None or r.t_done >= t0),
            "device": device, "checks": checks, "numbers": numbers,
            "extra": {"requests_done_in_window": len(done_in),
                      "checked_tokens": sum(len(o) for _, o in seqs)}}
