"""The one traffic generator: a mix file's parameters in, requests out.

A mix (``bench/mixes/<name>.json``) states:

- ``arrivals``: ``"backlog"`` keeps one full bucket of rows outstanding
  (offline bulk generation: every dispatch is a full ``max_batch``
  bucket); ``"clients"`` runs ``clients`` closed-loop users,
  each sending its next request when the last one returns;
- ``rows``: ``[lo, hi]``, rows per request, uniform. Under ``backlog`` the
  last request of each bucket is cut so the bucket holds exactly
  ``max_batch`` rows, so every seed offers the same work;
- ``steps`` and ``sampler``: the denoising loop; ``plan``: further plan
  fields (the rest keep the program's defaults);
- ``check_rows``: how many served rows the correctness check compares.

Noise comes from the seed on the device, labels and sizes from the seed on
the host. A request is due when its client's previous request completed
(at the window's start for the first), and no request is due after the
window's deadline. Latency runs from the due time to completion.
"""
from __future__ import annotations

import dataclasses
import queue
from typing import Any

import jax
import numpy as np

DISPATCH_TIMEOUT_S = 600.0  # longest wait for one completion before the run fails


def seed_key(seed: int, stream: int):
    """A raw PRNG key from any non-negative seed (wider than 32 bits too)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.numpy.asarray(words, dtype=jax.numpy.uint32)


@dataclasses.dataclass
class Request:
    index: int
    rows: int
    x: Any
    labels: Any
    due_t: float
    client: int = 0
    ticket: Any = None
    done_t: float | None = None
    error: str | None = None

    @property
    def latency_s(self) -> float:
        return self.done_t - self.due_t


def bucket_ladder(top: int) -> list[int]:
    out, b = [], 1
    while b <= top:
        out.append(b)
        b *= 2
    return out


class Traffic:
    def __init__(self, mix: dict, *, max_batch: int, latent_shape: tuple,
                 n_classes: int, seed: int):
        self.mix = mix
        self.max_batch = max_batch
        self.latent_shape = tuple(latent_shape)
        self.n_classes = n_classes
        self.rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
        self.key = seed_key(seed, 2)
        self.warm_key = seed_key(seed, 3)
        self.n_made = 0
        lo, hi = mix["rows"]
        if not 1 <= lo <= hi:
            raise ValueError(f"rows must be 1 <= lo <= hi, got {mix['rows']}")

    # ------------------------------------------------------------ requests
    def _inputs(self, key, index: int, rows: int):
        x = jax.random.normal(jax.random.fold_in(key, index), (rows,) + self.latent_shape)
        labels = jax.numpy.asarray(self.rng.integers(0, self.n_classes, rows), jax.numpy.int32)
        return x, labels

    def _request(self, rows: int, due_t: float, client: int = 0) -> Request:
        x, labels = self._inputs(self.key, self.n_made, rows)
        req = Request(self.n_made, rows, x, labels, due_t, client)
        self.n_made += 1
        return req

    def _size(self) -> int:
        lo, hi = self.mix["rows"]
        return int(self.rng.integers(lo, hi + 1))

    def _bucket(self, due_t: float) -> list[Request]:
        """Requests that fill exactly one ``max_batch`` bucket."""
        out, left = [], self.max_batch
        while left:
            rows = min(self._size(), left)
            out.append(self._request(rows, due_t))
            left -= rows
        return out

    def first_requests(self, rows: int) -> list[Request]:
        """The window's first requests, drawn as a run draws them, until they
        hold at least ``rows`` rows (for runs of the reference alone)."""
        out: list[Request] = []
        while sum(r.rows for r in out) < rows:
            out.extend(self._bucket(0.0) if self.mix["arrivals"] == "backlog"
                       else [self._request(self._size(), 0.0)])
        return out

    def buckets(self) -> list[int]:
        """Dispatch sizes the mix produces: the only shapes set-up warms."""
        if self.mix["arrivals"] == "backlog":
            return [self.max_batch]
        top = min(self.max_batch, self.mix["clients"] * self.mix["rows"][1])
        return bucket_ladder(1 << (top - 1).bit_length())  # up to the bucket of `top` rows

    # ----------------------------------------------------------------- runs
    def warm(self, scheduler) -> None:
        """Serve one dispatch of every bucket the window will use, so every
        shape it runs, eager calibration included, is compiled in set-up."""
        for i, b in enumerate(self.buckets()):
            x, labels = self._inputs(self.warm_key, i, b)
            scheduler.submit(x, labels).result(timeout=DISPATCH_TIMEOUT_S)

    def run(self, scheduler, t0: float, seconds: float,
            on_first_done=None) -> list[Request]:
        """Offer the mix from ``t0`` until ``t0 + seconds``, then wait for
        every outstanding request. ``scheduler`` must collect completed
        tickets (``collect_done=True``). ``on_first_done`` is called once,
        when the first request completes."""
        deadline = t0 + seconds
        arrivals = self.mix["arrivals"]
        done: list[Request] = []
        live: dict[int, Request] = {}

        def send(reqs):
            for r in reqs:
                r.ticket = scheduler.submit(r.x, r.labels)
                live[r.ticket.index] = r
                if arrivals == "clients":
                    try:  # mark it wanted now, so a partial bucket dispatches
                        r.ticket.result(timeout=0)
                    except TimeoutError:
                        pass

        if arrivals == "backlog":
            send(self._bucket(t0))
        elif arrivals == "clients":
            send([self._request(self._size(), t0, c) for c in range(self.mix["clients"])])
        else:
            raise ValueError(f"unknown arrivals {arrivals!r}")
        while live:
            try:
                ticket = scheduler.done.get(timeout=DISPATCH_TIMEOUT_S)
            except queue.Empty:
                raise TimeoutError(
                    f"no request completed within {DISPATCH_TIMEOUT_S} s") from None
            req = live.pop(ticket.index, None)
            if req is None:
                continue
            req.done_t = ticket.done_t
            try:
                ticket.result(timeout=0)
            except Exception as exc:  # noqa: BLE001 - a failed request is counted, not raised
                req.error = repr(exc)
            done.append(req)
            if on_first_done is not None and len(done) == 1:
                on_first_done()
            if req.done_t >= deadline:
                continue
            if arrivals == "backlog":
                if sum(r.rows for r in live.values()) < self.max_batch:
                    send(self._bucket(req.done_t))
            else:
                send([self._request(self._size(), req.done_t, req.client)])
        return sorted(done, key=lambda r: r.index)
