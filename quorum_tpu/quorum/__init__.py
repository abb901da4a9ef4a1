"""Native quorum serving (docs/quorum.md).

The paper's topology — fan one prompt out to N members, combine the
answers — rebuilt as a first-class serving workload instead of a proxy
pattern, in three layers that compose but ship independently:

  1. **Shared-prefix member dedup** (engine tier): on a ``members=M``
     stacked engine with ``member_seeds=shared``, a member-complete
     admission group carrying one prompt prefills it ONCE and broadcasts
     the K/V into all M cache rows — ``quorum_dedup=1`` on the engine
     URL; savings on ``quorum_tpu_quorum_dedup_tokens_total``. Lives in
     :mod:`quorum_tpu.engine.engine` (``_dedup_admit_fn``).

  2. **In-engine aggregation hop** (strategy tier): the aggregator's
     synthesis runs as an ordinary engine request with its own QoS class
     (``aggregator_priority``), optionally streamed live as the client
     response (``stream_aggregate``).
     Lives in :mod:`quorum_tpu.strategies.aggregate`.

  3. **Cross-cell quorum** (router tier, this package): a ``quorum=M``
     request fans out to M distinct ring-chosen replicas and combines at
     the tier that already owns failover. A member that dies
     mid-generation is first retried token-exact on a spare cell (the
     PR 19 resume wire contract), and only then DROPPED — the request is
     served from the survivors (``quorum_tpu_quorum_degraded_total``),
     never failed while any member holds content.
"""

from quorum_tpu.quorum.fanout import (
    MAX_QUORUM,
    QuorumLeg,
    choose_members,
    pop_quorum,
    quorum_complete,
    quorum_stream,
    validate_quorum,
)

__all__ = [
    "MAX_QUORUM",
    "QuorumLeg",
    "choose_members",
    "pop_quorum",
    "quorum_complete",
    "quorum_stream",
    "validate_quorum",
]
