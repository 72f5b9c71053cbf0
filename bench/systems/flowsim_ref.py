"""flowSim's plain reference: the max-min fair flow-level simulator of
arXiv:2503.01770 §2.1, one event at a time in numpy float64. It imports
nothing of the program.

At every arrival and every departure the rates of the active flows are
found anew by progressive filling. In each round, the capacity that a
link has left after the flows already fixed is shared equally among its
flows not yet fixed; each unfixed flow's share is the least over the
links of its path; the flows whose share is the round's least are fixed
at it. Rounds go on until every active flow is fixed. Between events the
active flows drain at their rates.

The next event is the next arrival when it comes no later than the
earliest departure; the departing flow is the active one with the least
remaining size over rate, the lowest flow id first on a tie. That is the
order in which the program takes events.

`precision` names what each round's rates are rounded to, on the bits,
before they are used: "float32", the precision the program keeps rates
in, or "bfloat16", one precision below it (the control). Times, sizes
and capacities stay float64.
"""
from __future__ import annotations

import numpy as np

# flows whose shares differ by less than this, relative, are fixed in one
# round: shares that are equal in exact arithmetic differ by a few float32
# spacings once rates rounded to float32 are taken from the capacities
TIE = 1e-6


def round_bf16(x: np.ndarray) -> np.ndarray:
    """To float32, then to bfloat16 on the bits: round to nearest, ties
    to even."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


ROUND = {"float32": lambda x: np.asarray(x, np.float32).astype(np.float64),
         "bfloat16": round_bf16}


def waterfill(cap: np.ndarray, flat: np.ndarray, owner: np.ndarray,
              k: int, rnd) -> tuple:
    """Max-min rates of k flows whose paths are the links `flat`, entry e
    belonging to flow `owner[e]`; and the number of filling rounds."""
    L = len(cap)
    rates = np.zeros(k)
    fixed = np.zeros(k, bool)
    rounds = 0
    while not fixed.all():
        live = ~fixed[owner]
        n_l = np.bincount(flat[live], minlength=L)
        used = np.bincount(flat[~live], weights=rates[owner[~live]],
                           minlength=L)
        avail = np.maximum(cap - used, 0.0)
        share = np.where(n_l > 0, avail / np.maximum(n_l, 1), np.inf)
        f_share = np.full(k, np.inf)
        np.minimum.at(f_share, owner[live], share[flat[live]])
        theta = f_share.min()
        newly = f_share <= theta * (1 + TIE)
        rates[newly] = rnd(f_share[newly])
        fixed |= newly
        rounds += 1
    return rates, rounds


def simulate(scen, precision: str = "float32") -> tuple:
    """(FCTs in seconds, stats) of a `bench.traffic.Scenario`: stats has
    the events, the filling rounds over all of them and the most rounds
    of one event. A flow that never departs has a NaN FCT."""
    rnd = ROUND[precision]
    n = scen.num_flows
    cap = np.asarray(scen.capacity, np.float64)
    paths = [np.asarray(p, np.int64) for p in scen.paths]
    bits = np.asarray(scen.size, np.float64) * 8.0
    t_arr = np.asarray(scen.t_arrival, np.float64)
    order = np.argsort(t_arr, kind="stable")
    remaining = np.zeros(n)
    rates = np.zeros(n)
    active = np.zeros(n, bool)
    fct = np.full(n, np.nan)
    t, ptr = 0.0, 0
    stats = {"events": 0, "rounds": 0, "rounds_max": 0}
    for _ in range(2 * n):
        next_arr = t_arr[order[ptr]] if ptr < n else np.inf
        live = active & (rates > 0)
        tta = np.full(n, np.inf)
        tta[live] = remaining[live] / rates[live]
        dep = int(np.argmin(tta))
        next_dep = t + tta[dep]
        if next_arr == np.inf and next_dep == np.inf:
            break
        arrival = next_arr <= next_dep
        t_ev = next_arr if arrival else next_dep
        remaining[active] -= rates[active] * (t_ev - t)
        t = t_ev
        if arrival:
            fid = int(order[ptr])
            ptr += 1
            active[fid] = True
            remaining[fid] = bits[fid]
        else:
            fid = dep
            active[fid] = False
            remaining[fid] = 0.0
            fct[fid] = t - t_arr[fid]
        idx = np.flatnonzero(active)
        rates[:] = 0.0
        rounds = 0
        if idx.size:
            flat = np.concatenate([paths[i] for i in idx])
            owner = np.repeat(np.arange(idx.size),
                              [len(paths[i]) for i in idx])
            rates[idx], rounds = waterfill(cap, flat, owner, idx.size, rnd)
        stats["events"] += 1
        stats["rounds"] += rounds
        stats["rounds_max"] = max(stats["rounds_max"], rounds)
    return fct, stats
