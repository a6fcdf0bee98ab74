"""Retrieval serving tier — the third leg of train -> validate -> serve.

The control plane (repro.control) knows the best checkpoint at every
moment; this package puts it behind a query endpoint without ever forking
the scoring math.  Three planes:

  * index   — :class:`~repro.serve.index.IndexBuilder` encodes the corpus
    once per promoted checkpoint through the SAME ``TokenStore`` /
    ``encode_store`` machinery the validator streams through, in the
    validator's chunk geometry, into a device-resident, chunk-major
    :class:`~repro.serve.index.ServingIndex`.
  * request — :class:`~repro.serve.service.QueryService` micro-batches
    queries (max-latency flush), encodes them with the same cached
    encoder, and folds the resident chunks through the validator's own
    retrieval stage (``make_stage``: the fused score, mask and top-k merge,
    rows past a chunk's end masked as the validator masks them) — so
    serving numbers ARE validation numbers, bit for bit (Kim et al. 2022's
    training-inference gap, closed by construction and locked by
    tests/test_serve_parity.py).
  * promotion — :class:`~repro.serve.promoter.Promoter` tails the control
    plane's fsync'd ``select`` events and hot-swaps the live index with a
    zero-downtime two-phase flip (build -> verify -> atomic pointer swap,
    mirroring ``ckpt.save``'s commit discipline), each swap recorded as a
    replayable JSONL event with checkpoint/engine/``score_dtype``
    provenance.

:class:`~repro.serve.admission.AdmissionController` bounds in-flight
requests so overload degrades by rejection, never by unbounded queueing.
"""

from repro.serve.admission import AdmissionController, ServeOverloaded
from repro.serve.index import IndexBuilder, ServeConfig, ServingIndex
from repro.serve.promoter import Promoter, replay_swaps
from repro.serve.service import QueryService, ServeResponse

__all__ = [
    "AdmissionController", "IndexBuilder", "Promoter", "QueryService",
    "ServeConfig", "ServeOverloaded", "ServeResponse", "ServingIndex",
    "replay_swaps",
]
