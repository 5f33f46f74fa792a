"""The one traffic generator: reads a mix file (``fdnbench/traffic/<name>.json``)
and turns ``--seed`` into admission batches.

A mix is data: its loop kind (closed bulk replay or open gateway), its
admission window, its rate, its popularity skew and its arrival process.
Every seed gets the same work: the Azure per-minute counts (drawn from a
seed of the mix file, in a fixed order) and the MMPP phase lengths (listed
in it, the seed orders them within each cycle) are fixed, and ``--seed``
draws the arrivals' times (and, for ``poisson`` and ``mmpp``, their
functions and counts).

Arrival processes (copied from the program's own trace library,
``repro.inspector.traces`` and ``repro.inspector.streaming.chunk_batch``,
so that a change there cannot move the yardstick):

* ``azure``: per-minute per-function Poisson counts, as the public Azure
  Functions 2019 dataset records them (Shahrad et al., ATC '20), spread
  uniformly inside their minute;
* ``poisson``: one Poisson stream over all functions;
* ``mmpp``: a two-state Markov-modulated Poisson process, quiet and burst
  phases (BurstGPT, arXiv:2401.17644).

Functions take Zipf shares in the configuration's order (most popular
first).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

BLOCK_S = 60.0   # arrivals are generated one minute of sim time at a time


def zipf_shares(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=float) ** s
    return w / w.sum()


def window_key(window_s: float) -> str:
    """How a configuration keys a rate by admission window: ``"5ms"``."""
    return f"{1e3 * window_s:g}ms"


def mix_rate(mix: Dict, config: Dict) -> float:
    """Mean offered rate (arrivals per sim-second): absolute, or a share of
    a rate the configuration records for the mix's admission window (its
    fleet's sustainable rate)."""
    rate = mix["rate"]
    if "rps" in rate:
        return float(rate["rps"])
    table = config[rate["of"]]
    return float(table[window_key(float(mix["window_s"]))]) * \
        float(rate["frac"])


def azure_minute_counts(rps: float, shares: np.ndarray, mult: np.ndarray,
                        rng: np.random.Generator) -> np.ndarray:
    """(F,) Poisson counts of one minute: each function's mean per-minute
    count, scaled by that minute's burst multiplier (the per-function
    Poisson draw of ``synthetic_azure_counts``)."""
    return rng.poisson(rps * 60.0 * shares * mult)


def counts_to_block(counts: np.ndarray, t0: float, span_s: float,
                    rng: np.random.Generator) -> Tuple[np.ndarray,
                                                       np.ndarray]:
    """Spread per-function counts uniformly over ``[t0, t0 + span_s)`` and
    stable-sort by time (``chunk_batch``'s expansion)."""
    fn_col = np.repeat(np.arange(counts.size, dtype=np.int32), counts)
    t_col = t0 + rng.random(int(counts.sum())) * span_s
    order = np.argsort(t_col, kind="stable")
    return fn_col[order], t_col[order]


class Traffic:
    """Arrivals of one run, generated a block at a time and handed out as
    ``InvocationBatch`` windows in time order."""

    def __init__(self, mix: Dict, config: Dict, specs: List, seed: int):
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"unknown loop kind {self.loop!r}")
        self.window_s = float(mix["window_s"])
        self.warmup_sim_s = float(mix["warmup_sim_s"])
        self.warmup_window_s = float(mix["warmup_window_s"])
        self.rps = mix_rate(mix, config)
        self.specs = specs
        self.shares = zipf_shares(len(specs),
                                  float(mix["popularity"]["zipf_s"]))
        self.arr = dict(mix["arrivals"])
        self.kind = self.arr["kind"]
        self.rng = np.random.default_rng(seed)
        if self.kind == "azure":
            self._init_azure()
        elif self.kind == "mmpp":
            self._init_mmpp()
        elif self.kind != "poisson":
            raise ValueError(f"unknown arrival kind {self.kind!r}")
        self._block_t = 0.0             # sim time generated up to
        self._blocks: List[object] = []  # (t_lo, t_hi, InvocationBatch)
        self._cursor = 0.0

    # --------------------------------------------------------- processes --
    def _init_azure(self):
        a = self.arr
        m, cv = int(a["minutes"]), float(a["minute_cv"])
        fixed = np.random.default_rng(int(a["shape_seed"]))
        k = 1.0 / (cv * cv)
        # the same minutes, in the same order, for every seed: a run covers
        # only a few of them, so another order would change its work
        mult = fixed.gamma(k, 1.0 / k, size=(len(self.specs), m))
        # the counts too: which minutes a closed-loop window covers depends
        # on how fast the host runs, so counts drawn from the run's seed
        # would change its work
        self._counts = np.stack([azure_minute_counts(
            self.rps, self.shares, mult[:, j], fixed) for j in range(m)], 1)
        self._minute = 0

    def _init_mmpp(self):
        a = self.arr
        quiet = np.asarray(a["quiet_s"], float)
        burst = np.asarray(a["burst_s"], float)
        ratio = float(a["burst_ratio"])
        q_tot, b_tot = quiet.sum(), burst.sum()
        base = self.rps * (q_tot + b_tot) / (q_tot + ratio * b_tot)
        # the same phases every cycle and every seed, in the seed's order
        quiet = quiet[self.rng.permutation(quiet.size)]
        burst = burst[self.rng.permutation(burst.size)]
        lens = np.empty(2 * quiet.size)
        lens[0::2], lens[1::2] = quiet, burst
        rates = np.empty(lens.size)
        rates[0::2], rates[1::2] = base, base * ratio
        self._phase_end = np.cumsum(lens)
        self._phase_rate = rates
        self._cycle_s = float(self._phase_end[-1])
        self.base_rps, self.burst_rps = base, base * ratio

    def _mmpp_block(self, t0: float) -> Tuple[np.ndarray, np.ndarray]:
        fns, ts = [], []
        t = t0
        while t < t0 + BLOCK_S:
            c0 = np.floor(t / self._cycle_s) * self._cycle_s
            i = min(int(np.searchsorted(self._phase_end, t - c0,
                                        side="right")),
                    self._phase_end.size - 1)
            # max(): a phase edge that rounds onto t still moves on
            end = max(min(c0 + self._phase_end[i], t0 + BLOCK_S),
                      np.nextafter(t, np.inf))
            n = int(self.rng.poisson(self._phase_rate[i] * (end - t)))
            ts.append(t + np.sort(self.rng.random(n)) * (end - t))
            fns.append(self.rng.choice(len(self.specs), n,
                                       p=self.shares).astype(np.int32))
            t = end
        return np.concatenate(fns), np.concatenate(ts)

    def _next_block(self):
        from repro.core.invocation_batch import InvocationBatch
        t0 = self._block_t
        if self.kind == "azure":
            counts = self._counts[:, self._minute % self._counts.shape[1]]
            self._minute += 1
            fn, t = counts_to_block(counts, t0, BLOCK_S, self.rng)
        elif self.kind == "poisson":
            n = int(self.rng.poisson(self.rps * BLOCK_S))
            t = t0 + np.sort(self.rng.random(n)) * BLOCK_S
            fn = self.rng.choice(len(self.specs), n,
                                 p=self.shares).astype(np.int32)
        else:
            fn, t = self._mmpp_block(t0)
        self._block_t = t0 + BLOCK_S
        self._blocks.append((t0, self._block_t,
                             InvocationBatch(list(self.specs), fn, t)))

    # -------------------------------------------------------------- take --
    def take(self, t_end: float):
        """All arrivals in ``[cursor, t_end)`` as one batch (a zero-copy
        view of its block when it lies in one), and advance the cursor."""
        from repro.core.invocation_batch import InvocationBatch
        while self._block_t < t_end:
            self._next_block()
        lo_t, self._cursor = self._cursor, t_end
        parts = []
        keep = []
        for blk in self._blocks:
            b_lo, b_hi, batch = blk
            if b_hi > lo_t:
                keep.append(blk)
            if b_hi <= lo_t or b_lo >= t_end:
                continue
            t = batch.arrival_t
            i = int(np.searchsorted(t, lo_t, side="left"))
            j = int(np.searchsorted(t, t_end, side="left"))
            parts.append(batch.view(i, j))
        self._blocks = keep
        if len(parts) == 1:
            return parts[0]
        return InvocationBatch(list(self.specs),
                               np.concatenate([p.fn_idx for p in parts]),
                               np.concatenate([p.arrival_t for p in parts]))

    def describe(self) -> Dict:
        out: Dict[str, Optional[float]] = {
            "loop": self.loop, "kind": self.kind, "rps": self.rps,
            "window_s": self.window_s}
        if self.kind == "mmpp":
            out.update(base_rps=self.base_rps, burst_rps=self.burst_rps,
                       cycle_s=self._cycle_s)
        return out
