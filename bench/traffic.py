"""The one traffic generator. A traffic mix is a data file of parameters
(``bench/traffic/<name>.json``, with a cell's own ``bench/cells/<cell>.json``
laid over it); this module reads every mix.

Kinds (``"kind"``):

- ``open_loop``: independent requests, Poisson arrivals at ``rate_per_s``.
- ``sessions``: sessions arrive Poisson at ``rate_per_s``. Each picks one
  of ``system_prompts`` shared prompts (Zipf, exponent ``zipf_s``) and
  makes ``turns`` turns; a turn's prompt is the system prompt, the
  history (earlier user messages and the served answers) and a new user
  message. A later turn is due ``think`` seconds (exponential) after the
  previous answer's last token.
- ``backlog``: an offline batch; at least ``queued`` requests always wait.

Lengths are ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
``{"dist": "loguniform", "min", "max"}``, rounded and clipped.

Every seed gets the same work. The sizes, gaps, system-prompt choices
and think times are drawn from the mix's fixed ``base_seed``, phase by
phase (pre-roll, window, drain), in the same order on every seed: a
fixed schedule, as a replayed trace is. The run's seed draws the token
ids (and, elsewhere, the weights). At this system's low knee a window
holds a few dozen requests, and permuting their order by seed moved the
90th percentile of time to first token by a factor of 2.8 between seeds
(PERF.md); the fixed schedule leaves the seed to change the content of
the work, not its amount or timing.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


def draw_lengths(rng, d: dict, n: int) -> np.ndarray:
    if d["dist"] == "lognormal":
        x = d["median"] * np.exp(d["sigma"] * rng.standard_normal(n))
    elif d["dist"] == "loguniform":
        x = np.exp(rng.uniform(np.log(d["min"]), np.log(d["max"]), n))
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(np.rint(x), d["min"], d["max"]).astype(np.int64)


def _gaps(rng, rate: float, horizon: float) -> np.ndarray:
    """Exponential gaps whose running sum stays below ``horizon``."""
    out, t = [], 0.0
    while True:
        g = rng.exponential(1.0 / rate)
        if t + g >= horizon:
            return np.asarray(out)
        t += g
        out.append(g)


@dataclasses.dataclass
class Item:
    """One request to submit."""
    due: float                 # seconds on the traffic clock
    prompt: np.ndarray
    max_new: int
    session: int = -1
    turn: int = 0


class Traffic:
    """Arrivals for one run: ``pop_due(now)`` returns the items due by
    ``now``; ``finished(item, tokens, now)`` lets a session schedule its
    next turn; ``next_due()`` says when the next item is due."""

    def __init__(self, p: dict, seed: int, vocab: int, *, window_s: float,
                 n_slots: int):
        self.p, self.vocab = p, vocab
        self.kind = p["kind"]
        self.rng = np.random.default_rng(seed)       # token ids
        base = np.random.default_rng(p["base_seed"])
        self.preroll = float(p["preroll_s"])
        self.window_s = float(window_s)
        self.pending: List[Item] = []
        self.queued_min = int(p.get("queued", n_slots))
        if self.kind == "sessions":
            self.system = [self.tokens(p["system_len"])
                           for _ in range(p["system_prompts"])]
            self.history = {}
        # Phase by phase: pre-roll, window, and the drain after it, each
        # its own draw from the base seed.
        t0 = 0.0
        for length in (self.preroll, self.window_s, p["drain_s"]):
            self._phase(base, t0, length)
            t0 += length
        self.pending.sort(key=lambda it: it.due)

    def tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, int(n)).astype(np.int32)

    def _phase(self, base, t0: float, length: float) -> None:
        p = self.p
        if self.kind == "backlog":
            n = int(p["per_phase"])
            ins = draw_lengths(base, p["prompt"], n)
            outs = draw_lengths(base, p["output"], n)
            self.backlog = getattr(self, "backlog", []) + [
                (int(a), int(b)) for a, b in zip(ins, outs)]
            return
        dues = t0 + np.cumsum(_gaps(base, p["rate_per_s"], length))
        n = len(dues)
        if self.kind == "open_loop":
            ins = draw_lengths(base, p["prompt"], n)
            outs = draw_lengths(base, p["output"], n)
            for d, a, b in zip(dues, ins, outs):
                self.pending.append(Item(float(d), self.tokens(a), int(b)))
            return
        if self.kind != "sessions":
            raise ValueError(f"unknown traffic kind {self.kind!r}")
        k = p["system_prompts"]
        zipf = 1.0 / np.arange(1, k + 1) ** p["zipf_s"]
        sysid = base.choice(k, size=n, p=zipf / zipf.sum())
        T = p["turns"]
        users = draw_lengths(base, p["user"], n * T).reshape(n, T)
        answers = draw_lengths(base, p["answer"], n * T).reshape(n, T)
        thinks = base.exponential(p["think_s"], (n, T))
        for j, d in enumerate(dues):
            sid = len(self.history)
            self.history[sid] = {
                "sys": int(sysid[j]), "users": users[j], "answers": answers[j],
                "thinks": thinks[j], "context": self.system[int(sysid[j])]}
            self.pending.append(self._turn(sid, 0, float(d)))

    def _turn(self, sid: int, turn: int, due: float) -> Item:
        h = self.history[sid]
        prompt = np.concatenate([h["context"], self.tokens(h["users"][turn])])
        h["context"] = prompt
        return Item(due, prompt, int(h["answers"][turn]), sid, turn)

    # -- the driver's side ---------------------------------------------
    def pop_due(self, now: float, n_queued: int = 0) -> List[Item]:
        if self.kind == "backlog":
            out = []
            while n_queued + len(out) < self.queued_min and self.backlog:
                a, b = self.backlog.pop(0)
                out.append(Item(now, self.tokens(a), b))
            return out
        i = 0
        while i < len(self.pending) and self.pending[i].due <= now:
            i += 1
        out, self.pending = self.pending[:i], self.pending[i:]
        return out

    def next_due(self) -> Optional[float]:
        return self.pending[0].due if self.pending else None

    def finished(self, item: Item, served: np.ndarray, now: float) -> None:
        """A session's answer is complete: its next turn is due after the
        think time, with the served answer in its history."""
        if self.kind != "sessions" or item.turn + 1 >= self.p["turns"]:
            return
        h = self.history[item.session]
        h["context"] = np.concatenate([item.prompt, served.astype(np.int32)])
        nxt = self._turn(item.session, item.turn + 1,
                         now + float(h["thinks"][item.turn]))
        j = 0
        while j < len(self.pending) and self.pending[j].due <= nxt.due:
            j += 1
        self.pending.insert(j, nxt)

    def longest_prompt(self) -> int:
        """The longest prompt this mix can make (for the warm-up)."""
        p = self.p
        if self.kind == "sessions":
            return p["system_len"] + p["turns"] * p["user"]["max"] + (
                p["turns"] - 1) * p["answer"]["max"]
        return p["prompt"]["max"]

    def shortest_prefill(self) -> int:
        """The fewest prompt tokens a prefill of this mix computes."""
        p = self.p
        if self.kind == "sessions":
            return p["user"]["min"]
        return p["prompt"]["min"]
