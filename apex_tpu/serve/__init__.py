"""apex_tpu.serve — continuous-batching + paged-KV serving engine.

The multi-tenant leg of the decode stack: thousands of sessions share
one preallocated HBM block pool (:mod:`pool`), a continuous-batching
scheduler re-packs the live set every tick (:mod:`scheduler`), and the
engine (:mod:`engine`) dispatches a small family of program kinds —
``prefill_step`` / ``decode_step`` / ``draft_prefill_step`` /
``spec_verify_step`` — through the one-runtime executor, inheriting
its step-cache keying, dispatch spans, donation policy and watchdog
heartbeats.  :mod:`disagg` splits the engine into a prefill phase and
a decode phase (optionally speculative, with a draft model served
int8 from its own pool) joined by the schema-3 streamed KV handoff.
:mod:`elastic` replicates the engine into a membership-backed
:class:`ServeFleet` — live session migration on host loss, SLO-aware
shedding, epoch-aware routing.  Shape discipline (bucketed operands,
traced request state) is enforced by the SERVE-SHAPE lint rule; see
docs/serving.md.
"""
from .disagg import DisaggregatedEngine
from .elastic import FleetMember, ServeFleet, StaleEpochError
from .engine import ServeEngine
from .pool import (BlockPool, BlockTable, NULL_BLOCK, blocks_for,
                   init_pool_buffer)
from .scheduler import Request, SLO_CLASSES, Scheduler, Session, bucket

__all__ = [
    "DisaggregatedEngine", "ServeEngine", "ServeFleet", "FleetMember",
    "StaleEpochError", "SLO_CLASSES", "Request", "Scheduler",
    "Session", "bucket", "BlockPool", "BlockTable", "NULL_BLOCK",
    "blocks_for",
    "init_pool_buffer",
]
