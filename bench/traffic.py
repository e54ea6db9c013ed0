"""The one traffic generator: it reads a mix file and yields requests.

A mix is a JSON file ``bench/mixes/<name>.json``. Its ``arrivals`` say how
requests come:

- ``{"process": "poisson", "rate_per_s": r}``: open loop. A run of ``T``
  seconds gets ``K = round(r * T)`` requests. Their arrival times are a
  Poisson process conditioned on ``K`` arrivals in ``T``: ``K`` sorted
  uniforms drawn once from the mix's ``pool_seed``, each carrying the pool's
  request of its rank. The run's seed only turns that schedule on a circle
  of length ``T``: it starts at a phase drawn from the seed. So every seed
  offers the same requests at the same spacings, bursts included, in
  another order; the seed does not change how much queueing the window
  holds.
- ``{"process": "mmpp", "rate_low_per_s", "rate_high_per_s", "dwell_low_s",
  "dwell_high_s"}``: open loop, a 2-state Markov-modulated Poisson process
  drawn from the run's seed (copied from ``repro.core.traffic``).
- ``{"process": "backlog"}``: closed, no arrival times. The harness keeps
  at least ``max_batch`` requests waiting, so every decode slot stays full.

An open-loop schedule repeats with the window's length as its period: the
``lead_in_s`` seconds before the window (at most one period; 0 where the
mix names none) hold the schedule's own last arrivals a period early, and
after the close it goes on with its first. Those requests are not
measured; they give the window's first and last arrivals the same queue
ahead and behind that the turn gives every other arrival, so a burst
turned to an edge of the window is not served faster.

``prompts`` names a JSONL pool in ``bench/mixes`` (rows with ``prompt`` and
``reply_bytes``). ``reply_tokens`` is ``{"from": "reply_bytes"}`` (the
length of the programmatic twin's reply to that prompt) or
``{"uniform": [lo, hi]}`` (evenly spaced lengths, dealt to the prompts in
an order drawn from ``pool_seed``). Prompt and reply length stay paired
whatever the run's seed.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import pathlib
import random
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

MIX_DIR = pathlib.Path(__file__).resolve().parent / "mixes"


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    idx: int
    due_s: Optional[float]      # offset from the window's open; None: backlog
    prompt: str
    max_new_tokens: int
    kind: str


def load_mix(name: str) -> Dict:
    path = MIX_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def load_pool(mix: Dict) -> List[Dict]:
    path = MIX_DIR / mix["prompts"]
    return [json.loads(line) for line in path.read_text().splitlines()
            if line.strip()]


def _reply_tokens(mix: Dict, rows: List[Dict]) -> List[int]:
    """Reply length of each row; fixed by the mix, never by the run's seed."""
    spec = mix["reply_tokens"]
    if "from" in spec:
        return [int(r[spec["from"]]) for r in rows]
    lo, hi = spec["uniform"]
    n = len(rows)
    fixed = [int(round(lo + (hi - lo) * i / max(n - 1, 1))) for i in range(n)]
    order = np.random.default_rng(mix["pool_seed"]).permutation(n)
    return [fixed[i] for i in order]


def poisson_conditioned(rate_per_s: float, seconds: float,
                        pool_seed: int) -> List[float]:
    """``round(rate * seconds)`` sorted arrival offsets in ``[0, seconds)``,
    drawn from ``pool_seed`` alone."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError(f"rate and seconds must be > 0: {rate_per_s}, "
                         f"{seconds}")
    k = max(1, int(round(rate_per_s * seconds)))
    u = np.sort(np.random.default_rng(pool_seed).uniform(0.0, 1.0, k))
    return [float(x) for x in u * seconds]


def turn(times: List[float], seconds: float,
         seed: int) -> List[Tuple[float, int]]:
    """``times`` on a circle of length ``seconds``, started at a phase drawn
    from ``seed``: (new offset, index in ``times``), in order of arrival."""
    phase = float(np.random.default_rng([seed, 1]).uniform(0.0, seconds))
    return sorted(((t - phase) % seconds, i) for i, t in enumerate(times))


def mmpp(rate_low: float, rate_high: float, seconds: float, dwell_low: float,
         dwell_high: float, seed: int) -> List[float]:
    """2-state MMPP arrivals over ``[0, seconds)`` (``MMPPTraffic``)."""
    rng = random.Random(seed)
    out: List[float] = []
    t, high = 0.0, False
    while t < seconds:
        end = min(t + rng.expovariate(1.0 / (dwell_high if high
                                             else dwell_low)), seconds)
        rate = rate_high if high else rate_low
        tt = t + rng.expovariate(rate)
        while tt < end:
            out.append(tt)
            tt += rng.expovariate(rate)
        t, high = end, not high
    return out


class Traffic:
    """The requests of one run of one mix, from ``seed``."""

    def __init__(self, mix: Dict, seconds: float, seed: int):
        self.mix = mix
        self.seconds = seconds
        self.seed = seed
        rows = load_pool(mix)
        rng = np.random.default_rng([seed, 0])
        arr = mix["arrivals"]
        self.open_loop = arr["process"] != "backlog"
        if arr["process"] == "poisson":
            # the pool's first K requests ride with the arrivals they were
            # drawn with, and turn with them
            due = turn(poisson_conditioned(arr["rate_per_s"], seconds,
                                           mix["pool_seed"]), seconds, seed)
        elif arr["process"] == "mmpp":
            # the first K requests of the pool, in an order drawn from the seed
            t = mmpp(arr["rate_low_per_s"], arr["rate_high_per_s"], seconds,
                     arr["dwell_low_s"], arr["dwell_high_s"], seed)
            due = list(zip(t, rng.permutation(len(t))))
        elif arr["process"] == "backlog":
            due = None
        else:
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        pairs = list(zip(rows, _reply_tokens(mix, rows)))
        if due is not None:
            picked = [(t, pairs[i % len(pairs)]) for t, i in due]
            self.specs = [RequestSpec(j, t, r["prompt"], n, r["kind"])
                          for j, (t, (r, n)) in enumerate(picked)]
            self.lead_in_s = min(float(mix.get("lead_in_s", 0.0)), seconds)
        else:
            self._cycle = [pairs[j] for j in rng.permutation(len(pairs))]
            self.specs = []
            self.lead_in_s = 0.0
        # the schedule's last arrivals, a period before the window
        self.lead_in = [s for s in self._period(-1)
                        if s.due_s >= -self.lead_in_s]

    def _period(self, k: int) -> List[RequestSpec]:
        """The window's requests ``k`` periods later."""
        return [dataclasses.replace(s, due_s=s.due_s + k * self.seconds)
                for s in self.specs]

    def after(self) -> Iterator[RequestSpec]:
        """The schedule past the close, period after period, without end
        (nothing where the window has no arrivals)."""
        if not self.specs:
            return
        for k in itertools.count(1):
            yield from self._period(k)

    def backlog(self) -> Iterator[RequestSpec]:
        """Backlog requests without end, cycling the seed's order."""
        i = 0
        while True:
            row, n = self._cycle[i % len(self._cycle)]
            yield RequestSpec(i, None, row["prompt"], n, row["kind"])
            i += 1
