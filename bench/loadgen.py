"""The one traffic generator: every mix is a JSON file of parameters
under ``traffic/`` that this module reads.

Kinds of mix:

- ``open_loop``: requests due on a fixed schedule (Poisson, or on/off
  bursts), submitted when due whatever the server's state.
- ``backlog``: an offline batch; the waiting queue is topped up to
  ``depth`` before every step, so it never drains.
- ``sessions``: multi-turn conversations. Session starts are open loop;
  each later turn is due ``think`` seconds after the previous answer, and
  its prompt is the previous prompt, the served answer and a new user turn.

Every seed gets the same work. Sizes are the quantiles
``(i + 0.5) / n`` of each distribution and gaps those of the exponential,
put in an order drawn from the mix's own ``order_seed`` (0 if absent), so
the schedule of sizes and due times is the same for every run seed; the
run seed draws the token ids (and the harness's checked sample). On a
TPU v5e, orders drawn from the run seed moved phi4-chat's p95 TTFT
nine-fold near its knee, and qwen3-docs-batch's tokens/s nearly four-fold,
between seeds in 20 s windows.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

_ND = NormalDist()


@dataclass
class Arrival:
    due: float                   # seconds after the window opens
    prompt: list[int]
    max_new: int
    session: int | None = None
    turn: int = 0
    meta: dict = field(default_factory=dict)


def rng_for(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  (int(seed) >> 32) & 0xFFFFFFFF,
                                  sum(map(ord, stream))])


def sizes(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer sizes: the quantiles of a lognormal (``median``,
    ``sigma``) or a uniform (``min``..``max``), clipped, in ``rng``'s order."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([_ND.inv_cdf(x) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    v = np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)
    return rng.permutation(v)


def arrival_times(spec: dict, seconds: float, rng) -> np.ndarray:
    """Due times in ``[0, seconds)``: ``rate_per_s * seconds`` arrivals at
    exponential-quantile gaps in ``rng``'s order, scaled to the window. With
    ``on_off``, each ``period_s`` is warped so that ``on_share`` of its
    arrivals fall into its first ("on") half."""
    n = max(1, round(spec["rate_per_s"] * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    t = t / gaps.sum() * seconds                   # uniform-intensity clock
    burst = spec.get("on_off")
    if burst:
        t = _on_off(t, burst["period_s"], burst["on_share"])
    return t


def _on_off(t: np.ndarray, period: float, on_share: float) -> np.ndarray:
    """Map a uniform-intensity clock onto one whose intensity is
    ``2 * on_share`` in the first half of each period and
    ``2 * (1 - on_share)`` in the second (the same count per period)."""
    k, r = np.divmod(t, period)
    frac = r / period                              # share of the period
    on = frac < on_share
    half = period / 2
    inner = np.where(on, frac / on_share * half,
                     half + (frac - on_share) / (1 - on_share) * half)
    return k * period + inner


def order_rng(mix: dict, stream: str) -> np.random.Generator:
    """The generator of sizes, gaps and their order: the mix's, not the
    run's, so that every run seed gets the same schedule."""
    return rng_for(mix.get("order_seed", 0), stream)


class OpenLoop:
    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        rng = order_rng(mix, "open_loop")
        due = arrival_times(mix["arrivals"], seconds, rng)
        n = len(due)
        plens = sizes(mix["prompt"], n, rng)
        outs = sizes(mix["output"], n, rng)
        toks = rng_for(seed, "tokens")
        self.pending = [Arrival(float(d), toks.integers(1, vocab, int(p)).tolist(),
                                int(o)) for d, p, o in zip(due, plens, outs)]
        self.i = 0

    def ready(self, now: float, queued: int) -> list[Arrival]:
        out = []
        while self.i < len(self.pending) and self.pending[self.i].due <= now:
            out.append(self.pending[self.i])
            self.i += 1
        return out

    def next_due(self) -> float | None:
        return self.pending[self.i].due if self.i < len(self.pending) else None

    def finished(self, arrival: Arrival, served: list[int], now: float) -> None:
        pass


class Backlog:
    """Documents in blocks of ``block``: each block holds the same
    quantile sizes, each block in its own order."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        self.mix = mix
        self.rng = order_rng(mix, "backlog")
        self.toks = rng_for(seed, "tokens")
        self.vocab = vocab
        self.buf: list[Arrival] = []

    def _refill(self, now: float) -> None:
        n = self.mix.get("block", 64)
        plens = sizes(self.mix["prompt"], n, self.rng)
        outs = sizes(self.mix["output"], n, self.rng)
        self.buf = [Arrival(now, self.toks.integers(1, self.vocab, int(p)).tolist(),
                            int(o)) for p, o in zip(plens, outs)][::-1]

    def ready(self, now: float, queued: int) -> list[Arrival]:
        out = []
        for _ in range(max(0, self.mix["depth"] - queued)):
            if not self.buf:
                self._refill(now)
            a = self.buf.pop()
            a.due = now
            out.append(a)
        return out

    def next_due(self) -> float | None:
        return None

    def finished(self, arrival: Arrival, served: list[int], now: float) -> None:
        pass


class Sessions:
    """Session starts are open loop; turns follow answers after a think
    time. Per session: a system prompt (Zipf over ``system.count``), a
    number of turns, and for each turn a user length, an answer length, a
    think time and whether the answer is regenerated (the same prompt
    asked once more), all from fixed sets in seeded order."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        rng = order_rng(mix, "sessions")
        toks = rng_for(seed, "tokens")
        starts = arrival_times(mix["starts"], seconds, rng)
        n = len(starts)
        sysp = mix["system"]
        w = np.array([1 / (k + 1) ** sysp["zipf_s"] for k in range(sysp["count"])])
        counts = _largest_remainder(n * w / w.sum())
        which = rng.permutation(np.repeat(np.arange(sysp["count"]), counts))
        self.system = [toks.integers(1, vocab, sysp["tokens"]).tolist()
                       for _ in range(sysp["count"])]
        turns = sizes(mix["turns"], n, rng)
        total = int(turns.sum())
        users = iter(sizes(mix["user"], total, rng))
        answers = iter(sizes(mix["answer"], total, rng))
        q = (np.arange(total) + 0.5) / total
        th = mix["think_s"]
        thinks = iter(rng.permutation(th["min"] + q * (th["max"] - th["min"])))
        n_regen = round(mix.get("regenerate_share", 0.0) * total)
        regens = iter(rng.permutation(np.arange(total) < n_regen))
        self.max_prompt = mix["max_prompt"]
        self.toks = toks
        self.vocab = vocab
        self.sessions = []
        for i in range(n):
            plan = [(int(next(users)), int(next(answers)), float(next(thinks)),
                     bool(next(regens))) for _ in range(int(turns[i]))]
            self.sessions.append({"start": float(starts[i]),
                                  "system": int(which[i]), "plan": plan})
        # (due, tie-break, session id or a later turn's Arrival)
        self.timeline: list = sorted(
            (s["start"], i, i) for i, s in enumerate(self.sessions))
        self.order = len(self.timeline)

    def _turn(self, sid: int, turn: int, due: float,
              history: list[int]) -> Arrival | None:
        plan = self.sessions[sid]["plan"]
        if turn >= len(plan):
            return None
        ulen, alen = plan[turn][:2]
        prompt = history + self.toks.integers(1, self.vocab, ulen).tolist()
        if len(prompt) > self.max_prompt:
            return None
        return Arrival(due, prompt, alen, session=sid, turn=turn)

    def ready(self, now: float, queued: int) -> list[Arrival]:
        out = []
        while self.timeline and self.timeline[0][0] <= now:
            due, _, what = heapq.heappop(self.timeline)
            if isinstance(what, Arrival):
                out.append(what)
                continue
            history = list(self.system[self.sessions[what]["system"]])
            a = self._turn(what, 0, due, history)
            if a is not None:
                out.append(a)
        return out

    def next_due(self) -> float | None:
        return self.timeline[0][0] if self.timeline else None

    def finished(self, arrival: Arrival, served: list[int], now: float) -> None:
        sid = arrival.session
        _, alen, think, regen = self.sessions[sid]["plan"][arrival.turn]
        if regen and not arrival.meta.get("regenerated"):
            # the user asks for another answer: the same prompt again,
            # whose pages the trie now holds whole (copy on write)
            nxt = Arrival(now + think, arrival.prompt, alen, session=sid,
                          turn=arrival.turn, meta={"regenerated": True})
        else:
            nxt = self._turn(sid, arrival.turn + 1, now + think,
                             arrival.prompt + list(served))
        if nxt is not None:
            self.order += 1
            heapq.heappush(self.timeline, (nxt.due, self.order, nxt))


def _largest_remainder(x: np.ndarray) -> np.ndarray:
    base = np.floor(x).astype(int)
    short = int(round(x.sum())) - base.sum()
    base[np.argsort(-(x - base))[:short]] += 1
    return base


KINDS = {"open_loop": OpenLoop, "backlog": Backlog, "sessions": Sessions}


def source(mix: dict, seed: int, seconds: float, vocab: int):
    return KINDS[mix["kind"]](mix, seed, seconds, vocab)
