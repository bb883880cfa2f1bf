"""Topic-inference serving endpoint over a fitted :class:`EnforcedNMF`.

Requests carry a bag-of-words document (sparse ``(term_id, weight)``
pairs); the server micro-batches them into one padded-CSR matrix per tick
and folds the whole batch into the fitted topic space with a single
frozen-``U`` ``transform`` pass — so serving cost per tick is one (k x k)
solve plus one sparse matmul regardless of how many documents share the
batch.

Continuous refresh: served documents accumulate in a buffer and
:meth:`TopicServer.refresh` streams them back into the model through one
``partial_fit`` (the online sufficient-statistics engine,
:mod:`repro.core.online`) — so the topic space tracks the live traffic
distribution without ever re-running a batch fit.  ``refresh_every`` makes
this automatic; with the estimator configured for mesh streaming
(``solver="streaming"``, non-1x1 ``mesh_shape``) the refresh update runs
shard_mapped over the device grid.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.sparse.csr import from_coo

__all__ = ["TopicRequest", "TopicServer"]


@dataclasses.dataclass
class TopicRequest:
    rid: int
    #: sparse bag-of-words: (term_id, weight) pairs
    terms: Sequence[Tuple[int, float]]
    #: how many top topics to return
    top: int = 3
    #: result — [(topic_id, loading), ...], strongest first
    topics: Optional[List[Tuple[int, float]]] = None
    #: set instead of ``topics`` when the request is malformed (the
    #: serving-layer 400): the request is answered and excluded from the
    #: fold-in buffer, and the rest of its batch serves normally
    error: Optional[str] = None


class TopicServer:
    """Micro-batching fold-in server.

    >>> server = TopicServer(fitted_model, max_batch=32)
    >>> server.submit(TopicRequest(rid=0, terms=[(12, 2.0), (80, 1.0)]))
    >>> results = server.run_until_drained()
    >>> server.refresh()          # fold served docs back into the model

    ``refresh_every`` (documents) triggers :meth:`refresh` automatically
    from inside :meth:`step`; ``None`` leaves refresh manual.  The buffer
    of served documents is bounded by ``refresh_buffer`` (oldest dropped),
    so a long-running server that never refreshes holds at most that many
    term lists.
    """

    def __init__(self, estimator, max_batch: int = 32,
                 refresh_every: Optional[int] = None,
                 refresh_buffer: int = 4096):
        if getattr(estimator, "u_", None) is None:
            raise ValueError("TopicServer needs a fitted EnforcedNMF")
        self.estimator = estimator
        self.max_batch = max_batch
        self.n_terms = estimator.n_features_
        self.queue: List[TopicRequest] = []
        self.served = 0
        self.refresh_every = refresh_every
        self.refreshed = 0
        #: requests answered with an ``error`` instead of topics
        self.rejected = 0
        #: refresh attempts rolled back (exception or unhealthy factors)
        self.refresh_failures = 0
        #: served documents awaiting the next model refresh (bounded;
        #: oldest documents age out once past refresh_buffer).  An
        #: auto-refresh threshold implies at least that much buffer, or
        #: the trigger could never fire.
        self._refresh_buf: Deque[Sequence[Tuple[int, float]]] = deque(
            maxlen=max(int(refresh_buffer), int(refresh_every or 0), 1))

    def submit(self, req: TopicRequest):
        self.queue.append(req)

    def _validate(self, req: TopicRequest) -> Optional[str]:
        """The request's 400 reason, or None when it is servable.  Checked
        per request so one malformed document cannot poison its batch's
        packed matrix or kill the serving tick."""
        try:
            pairs = list(req.terms)
        except TypeError:
            return f"terms is not iterable ({type(req.terms).__name__})"
        if not pairs:
            return "empty document (no terms)"
        for entry in pairs:
            try:
                term, weight = entry
                term, weight = int(term), float(weight)
            except (TypeError, ValueError):
                return f"term entry {entry!r} is not a (term_id, weight) pair"
            if not math.isfinite(weight):
                return f"term {term} has non-finite weight {weight!r}"
        if not any(0 <= int(t) < self.n_terms for t, _ in pairs):
            return (f"no term id falls inside the model vocabulary "
                    f"[0, {self.n_terms})")
        return None

    def _pack_terms(self, term_lists: Sequence[Sequence[Tuple[int, float]]]):
        """Bag-of-words term lists -> one (n_terms, n_docs) padded-CSR
        matrix (out-of-vocabulary term ids dropped) — shared by the serve
        micro-batch and the refresh chunk."""
        rows, cols, vals = [], [], []
        for doc, terms in enumerate(term_lists):
            for term, weight in terms:
                if 0 <= term < self.n_terms:
                    rows.append(term)
                    cols.append(doc)
                    vals.append(float(weight))
        return from_coo(
            np.asarray(rows, np.int64), np.asarray(cols, np.int64),
            np.asarray(vals, np.float32), (self.n_terms, len(term_lists)),
        )

    def refresh(self, iters: Optional[int] = None,
                forget: float = 1.0) -> int:
        """Stream the documents served since the last refresh back into the
        estimator with one ``partial_fit`` — continuous topic-model refresh
        over the live traffic.  Returns the number of documents folded in
        (0 when the buffer is empty).  ``iters`` / ``forget`` pass through
        to :meth:`repro.nmf.EnforcedNMF.partial_fit`.

        The update is transactional: the pre-refresh factors and streaming
        accumulators are snapshotted first, and an update that throws or
        leaves the model unhealthy (non-finite factors — ``health_ >= 0``)
        is rolled back, the documents are re-buffered for the next attempt,
        and the server keeps serving on the last good topic space
        (``refresh_failures`` counts these)."""
        if not self._refresh_buf:
            return 0
        docs = list(self._refresh_buf)
        self._refresh_buf.clear()
        est = self.estimator
        snap = {name: getattr(est, name, None)
                for name in ("u_", "v_", "_av_acc", "_gv_acc",
                             "n_docs_seen_", "health_")}
        try:
            est.partial_fit(self._pack_terms(docs), iters=iters,
                            forget=forget)
            if int(getattr(est, "health_", -1)) >= 0:
                raise RuntimeError(
                    "partial_fit produced non-finite factors "
                    f"(health_={int(est.health_)})")
        except Exception as exc:
            for name, val in snap.items():
                setattr(est, name, val)
            self._refresh_buf.extend(docs)  # retry on the next refresh
            self.refresh_failures += 1
            warnings.warn(
                f"topic refresh over {len(docs)} document(s) failed and was "
                f"rolled back; serving continues on the previous topic "
                f"space ({exc})", RuntimeWarning)
            return 0
        self.refreshed += len(docs)
        return len(docs)

    def step(self) -> Dict[int, List[Tuple[int, float]]]:
        """Serve one micro-batch; returns ``{rid: [(topic, loading), ...]}``."""
        if not self.queue:
            return {}
        batch, self.queue = self.queue[: self.max_batch], self.queue[self.max_batch:]
        out = {}
        good = []
        for req in batch:
            reason = self._validate(req)
            if reason is None:
                good.append(req)
            else:
                req.error = reason
                req.topics = []
                out[req.rid] = []
                self.rejected += 1
        if not good:
            self.served += len(batch)
            return out
        a_new = self._pack_terms([req.terms for req in good])
        v = self.estimator.transform(a_new)          # (batch, k)
        order = np.asarray(jnp.argsort(-v, axis=1))
        v_np = np.asarray(v)
        for doc, req in enumerate(good):
            picks = [
                (int(t), float(v_np[doc, t]))
                for t in order[doc, : req.top]
                if v_np[doc, t] > 0
            ]
            req.topics = picks
            out[req.rid] = picks
        self.served += len(batch)
        self._refresh_buf.extend(req.terms for req in good)
        if (self.refresh_every is not None
                and len(self._refresh_buf) >= self.refresh_every):
            self.refresh()
        return out

    def run_until_drained(self, max_ticks: int = 1000) -> List[TopicRequest]:
        done: List[TopicRequest] = []
        for _ in range(max_ticks):
            if not self.queue:
                break
            n_before = len(self.queue)
            batch = self.queue[: self.max_batch]
            self.step()
            done.extend(batch)
            assert len(self.queue) < n_before  # step always drains
        return done
