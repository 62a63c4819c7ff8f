"""Named host spans in the profiler's trace.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` with ``args`` attached. It is recorded only while a
profiler trace is active (``jax.profiler.trace`` or ``start_trace``),
on the same clock as the device's ``XLA Ops`` and ``XLA Modules``
lines; otherwise it costs one activity check.

Every span, indented under its parent:

    train.outer_step (step)       train/loop.py ElasticTrainer.run
      train.feed                  batch provider or token pipelines
      train.inner_phase           dispatch of the inner-phase program(s)
      train.outer_sync            the boundary's sync, any schedule
        sync.pseudograds          core/diloco.py _sim_pseudograds
        sync.hop (hop, kind)      core/ring_reduce.py RingSyncOp.step
        sync.apply                core/diloco.py _finish_apply
      train.readback              the host read of the loss
      train.bookkeeping           history record, stream join, hooks,
                                  checkpoint submit
    engine.step                   serving/engine.py ContinuousEngine.step
      engine.admit
        engine.prefill (rid, width)
        engine.install (rid, slot)
      engine.decode_chunk         dispatch of the decode chunk
      engine.readback             the one blocking token-block read
      engine.drain

Under ``overlap="delayed"`` the ring hops of an in-flight sync are
dispatched between inner-phase chunks, so ``sync.hop`` also sits under
``train.inner_phase``, and the last drain of ``run`` under a
``train.outer_sync`` of its own; with ``overlap_admission`` the
early prefills sit under ``engine.step``.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
