"""Streaming ingestion subsystem (paper §4.5, closed re-optimization loop).

Replaces the per-row ``core.updates.UpdatableSynopsis`` hot path with fully
vectorized batched inserts and delta-merge serving (DESIGN.md §6):

* :mod:`ingest`  — ``StreamingIngestor``: one-pass batch routing against the
  leaf boxes, leaf aggregate deltas through the registry-dispatched
  ``segment_reduce`` kernel, and batched Vitter reservoir replacement with a
  single scatter-max + gather.
* :mod:`delta`   — delta-merge: the immutable base synopsis combined with
  the small device-resident delta (mergeable summaries, §2.4) into a
  serving-ready :class:`~repro.core.types.Synopsis` without re-uploading
  O(K) state per batch.
* :mod:`policy`  — drift signals (``staleness``, out-of-box fraction) and
  the on-device re-optimization loop: ``dp_monotone_jnp`` over (at most
  ``OPT_SAMPLES`` of) the live reservoir pool -> fresh cuts -> rebuild + sample re-stratification.
* :mod:`join_ingest` — ``JoinStreamingIngestor``: the base transition plus
  streamed (stratum x dim-partition) cell aggregates and keyed universe-
  sample appends for fk-join serving (DESIGN.md §13).
"""
from .ingest import StreamingIngestor, StreamState, ingest_batch_reference
from .delta import merge_synopsis, subtree_leaf_matrix, reservoir_moments
from .policy import DriftPolicy, reoptimize_cuts, reoptimize
from .join_ingest import JoinStreamingIngestor, JoinStreamState

__all__ = [
    "StreamingIngestor", "StreamState", "ingest_batch_reference",
    "merge_synopsis", "subtree_leaf_matrix", "reservoir_moments",
    "DriftPolicy", "reoptimize_cuts", "reoptimize",
    "JoinStreamingIngestor", "JoinStreamState",
]
