"""Open-loop arrivals: when each task is due, which payload it carries, and
the seed its operands are made from.

A broker's tenants are independent, so tasks arrive on a schedule whatever
the completions (an open loop), as a Poisson process: ``rate_per_s *
seconds`` arrivals at independent uniform times in the window, which is a
Poisson process held to that count, each carrying a payload drawn by the
traffic's weights (exact counts, in random order).

Every seed replays the same such draw, made once from a fixed stream, in
another order: the seed turns the schedule round by a random shift (an
arrival due past the window's end comes round to its start) and draws the
operand seeds.  Independent draws for every seed move a tail at 0.8 of the
knee by 20-40% from seed to seed (a simulation of the measured service
times), which no bound of 25% or less can hold; one draw replayed keeps the
Poisson process's clusters and gaps at every scale shorter than the window
and leaves the seed only where in the draw the window opens.

Traffic file keys: ``rate_per_s``, ``weights`` (payload name -> share) and
``operand_seeds`` (how many distinct operand seeds each payload cycles
through).
"""
from __future__ import annotations

import random
from dataclasses import dataclass

SEED_LIMIT = 2**31  # operand seeds travel as int32


@dataclass(frozen=True)
class Arrival:
    due_s: float  # seconds after the window opens
    payload: str  # name in the configuration's payload catalogue
    operand_seed: int


def operand_seeds(seed: int, n: int) -> list[int]:
    """``n`` distinct operand seeds drawn from the run's seed."""
    rng = random.Random(f"operands:{seed}")
    return rng.sample(range(SEED_LIMIT), n)


def apportion(n: int, weights: dict[str, float]) -> dict[str, int]:
    """Largest-remainder split of ``n`` tasks by weight."""
    total = sum(weights.values())
    names = sorted(weights)
    exact = {k: n * weights[k] / total for k in names}
    counts = {k: int(exact[k]) for k in names}
    left = n - sum(counts.values())
    for k in sorted(names, key=lambda k: (counts[k] - exact[k], k))[:left]:
        counts[k] += 1
    return counts


def poisson(traffic: dict, seconds: float) -> list[tuple[float, str]]:
    """The one draw every seed replays: (due time, payload) in time order."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    draw = random.Random(f"arrivals:{n}:{seconds!r}")
    dues = sorted(draw.uniform(0.0, seconds) for _ in range(n))
    counts = apportion(n, {k: w for k, w in traffic["weights"].items() if w > 0})
    kinds = [k for k in sorted(counts) for _ in range(counts[k])]
    draw.shuffle(kinds)
    return list(zip(dues, kinds))


def schedule(traffic: dict, seed: int, seconds: float) -> list[Arrival]:
    rng = random.Random(f"schedule:{seed}")
    shift = rng.uniform(0.0, seconds)
    # ``x % seconds`` of a tiny negative ``x`` rounds to ``seconds`` itself
    turned = sorted((((due - shift) % seconds) % seconds, kind) for due, kind in poisson(traffic, seconds))
    seeds = operand_seeds(seed, traffic["operand_seeds"])
    turn = {k: rng.randrange(len(seeds)) for k in sorted({k for _, k in turned})}
    out = []
    for due, kind in turned:
        out.append(Arrival(due, kind, seeds[turn[kind] % len(seeds)]))
        turn[kind] += 1
    return out
