"""The one traffic generator: a mix file of parameters -> a fixed request plan.

A mix (``traffic/<name>.json``) describes sessions of one or more turns:

* ``system_prompt_tokens``: a prompt every session starts with (0: none).
  It is prefilled during set-up, so the prefix cache holds it.
* ``turns``: turns per session.  Turn ``k`` sends the system prompt, the
  session's history (every earlier turn's new tokens and reply) and
  ``new_tokens`` fresh tokens, and asks for ``output_tokens`` tokens.
* ``think_s``: the next turn is due this long after the previous reply.
* ``new_tokens``/``output_tokens``: ``[lo, hi]`` ranges.  Lengths sit at the
  quantiles ``(i + 0.5) / n`` of a log-uniform law over the range
  (``n = quantile_points``), dealt in a fixed order.
* ``max_batch``: requests admitted at once (prefilling or decoding).

Sessions start open-loop, evenly spaced at the cell's ``rate`` per second.
Everything in the plan -- lengths, order, start times -- is a function of
the mix and the rate alone.  The seed chooses only token ids
(:func:`token_ids`), so every seed offers the same work.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Mix:
    system_prompt_tokens: int
    turns: int
    new_tokens: tuple[int, int]
    output_tokens: tuple[int, int]
    think_s: float
    max_batch: int
    quantile_points: int


@dataclasses.dataclass(frozen=True)
class Turn:
    new_tokens: int
    output_tokens: int


@dataclasses.dataclass(frozen=True)
class SessionPlan:
    index: int
    start_s: float
    turns: tuple[Turn, ...]


def load_mix(name: str, root: pathlib.Path = HERE) -> Mix:
    d = json.loads((root / "traffic" / f"{name}.json").read_text())
    return Mix(
        system_prompt_tokens=int(d["system_prompt_tokens"]),
        turns=int(d["turns"]),
        new_tokens=tuple(d["new_tokens"]),
        output_tokens=tuple(d["output_tokens"]),
        think_s=float(d["think_s"]),
        max_batch=int(d["max_batch"]),
        quantile_points=int(d["quantile_points"]),
    )


def log_uniform_quantiles(lo: int, hi: int, n: int) -> list[int]:
    """Lengths at the quantiles (i + 0.5) / n of a log-uniform law."""
    a, b = math.log(lo), math.log(hi)
    return [int(round(math.exp(a + (i + 0.5) / n * (b - a)))) for i in range(n)]


def dealing_order(n: int, stride: int) -> list[int]:
    """A fixed permutation of range(n): index k -> k * stride mod n, with
    the stride moved up to the next value coprime to n."""
    while math.gcd(stride, n) != 1:
        stride += 1
    return [(k * stride) % n for k in range(n)]


def plan_sessions(mix: Mix, rate: float, horizon_s: float) -> list[SessionPlan]:
    """Every session that starts before ``horizon_s``, in start order."""
    n = mix.quantile_points
    new_q = log_uniform_quantiles(*mix.new_tokens, n)
    out_q = log_uniform_quantiles(*mix.output_tokens, n)
    # Two strides near n/phi and n/phi^2 keep the two lengths of a turn
    # apart from each other and from the turn before.
    new_order = dealing_order(n, max(1, round(n * 0.618)))
    out_order = dealing_order(n, max(1, round(n * 0.382)))
    sessions = []
    count = int(math.ceil(horizon_s * rate))
    for i in range(count):
        turns = []
        for k in range(mix.turns):
            j = i * mix.turns + k
            turns.append(
                Turn(new_q[new_order[j % n]], out_q[out_order[j % n]])
            )
        sessions.append(SessionPlan(i, i / rate, tuple(turns)))
    return sessions


def token_ids(seed: int, stream: tuple[int, ...], n: int, vocab: int) -> list[int]:
    """``n`` token ids for one stream of the run, chosen by the seed."""
    rng = np.random.default_rng([int(seed) & (2**63 - 1), *stream])
    return rng.integers(2, vocab, size=n).tolist()


def system_prompt(mix: Mix, seed: int, vocab: int) -> list[int]:
    return token_ids(seed, (0,), mix.system_prompt_tokens, vocab)


def turn_tokens(seed: int, session: int, turn: int, n: int, vocab: int) -> list[int]:
    return token_ids(seed, (1, session, turn), n, vocab)
