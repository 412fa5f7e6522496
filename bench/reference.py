"""The plain reference: the divisible-load schedule LP and its ASAP replay,
written straight from the platform model and independent of the program.

A problem is the plain dict the traffic generator makes (``topology``,
``w``, ``z``, ``latency``, ``tau``, ``v_comm``, ``v_comp``, ``release``,
``return_ratio``), one installment per load, so cell ``t`` is load ``t``.
Processor ``i`` computes a unit of work in ``w[i]`` seconds; link ``i``
(chain: ``P_i -> P_{i+1}``; star: master -> worker ``i+1``) moves a unit of
data in ``z[i]`` seconds after a start-up ``latency[i]``.  On a chain link
``i`` forwards everything meant for processors past it; on a star it carries
its worker's own share, and the master's one port sends (and, for results,
receives) one message at a time, cells in order, workers in order.  A load
with ``return_ratio > 0`` sends ``return_ratio * v_comm * share`` bytes of
results back to the source after the share is computed.

``replay`` is the as-soon-as-possible execution of given fractions;
``solve`` is the least makespan over all fractions, a linear program solved
by SciPy's HiGHS.  Nothing here imports the program under test.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.optimize import linprog

__all__ = ["replay", "solve"]


def _arrays(p: dict, dtype) -> dict:
    return {k: np.asarray(p[k], dtype=dtype) for k in
            ("w", "z", "latency", "tau", "v_comm", "v_comp", "release",
             "return_ratio")}


def _volumes(star: bool, g: np.ndarray) -> np.ndarray:
    """[m-1, T] share of each cell's data that crosses link i."""
    if star:
        return g[1:]
    return np.cumsum(g[::-1], axis=0)[::-1][1:]


def replay(p: dict, gamma, dtype=np.float64) -> float:
    """Makespan of the as-soon-as-possible execution of ``gamma`` [m, T],
    every time computed in ``dtype``."""
    a = _arrays(p, dtype)
    g = np.asarray(gamma, dtype=dtype)
    m, T = g.shape
    star = p["topology"] == "star"
    returns = bool(np.any(a["return_ratio"] > 0)) and m > 1
    vol = _volumes(star, g)
    dcomm = (a["z"][:, None] * a["v_comm"][None, :] * vol
             + a["latency"][:, None])
    dret = (a["z"][:, None] * (a["return_ratio"] * a["v_comm"])[None, :] * vol
            + a["latency"][:, None])
    dcomp = a["w"][:, None] * a["v_comp"][None, :] * g
    zero = dtype(0)
    ce = np.zeros((m - 1, T), dtype)
    pe = np.zeros((m, T), dtype)
    re = np.zeros((m - 1, T), dtype)
    port = zero  # end of the master's last send (star)
    for t in range(T):
        rel = a["release"][t]
        for i in range(m - 1):
            if star:
                lo = max(rel, port)
            else:
                lo = rel if i == 0 else ce[i - 1, t]
                if t:
                    lo = max(lo, ce[i, t - 1])
                    if i + 1 <= m - 2:
                        lo = max(lo, ce[i + 1, t - 1])
            ce[i, t] = lo + dcomm[i, t]
            port = ce[i, t]
        for i in range(m):
            lo = a["tau"][i] if t == 0 else pe[i, t - 1]
            lo = max(lo, rel if i == 0 else ce[i - 1, t])
            pe[i, t] = lo + dcomp[i, t]
    mk = pe[:, T - 1].max() if T else zero
    if returns:
        port = zero  # end of the master's last receive (star)
        for t in range(T):
            order = range(m - 1) if star else range(m - 2, -1, -1)
            for i in order:
                lo = pe[i + 1, t]
                if star:
                    lo = max(lo, port)
                else:
                    if i + 1 <= m - 2:
                        lo = max(lo, re[i + 1, t])
                    if t:
                        lo = max(lo, re[i, t - 1])
                re[i, t] = lo + dret[i, t]
                port = re[i, t]
        mk = max(mk, re.max())
    return float(mk)


class _Layout:
    """Column numbers of the LP's variables: message starts ``cs``, compute
    starts ``ps``, fractions ``g``, result-message starts ``rs`` (with
    returns) and the makespan ``mk``."""

    def __init__(self, m: int, T: int, returns: bool):
        self.m, self.T = m, T
        self.cs = np.arange((m - 1) * T).reshape(m - 1, T)
        base = self.cs.size
        self.ps = base + np.arange(m * T).reshape(m, T)
        base += self.ps.size
        self.g = base + np.arange(m * T).reshape(m, T)
        base += self.g.size
        self.rs = base + np.arange((m - 1) * T * returns).reshape(
            m - 1 if returns else 0, T)
        base += self.rs.size
        self.mk = base
        self.n = base + 1


def _rows(p: dict):
    """The LP as (layout, rows, eq rows); a row is (terms, const) meaning
    ``sum(coef * x[col]) + const <= 0``, an eq row ``sum(...) == rhs``."""
    a = _arrays(p, np.float64)
    m, T = len(a["w"]), len(a["v_comp"])
    star = p["topology"] == "star"
    returns = bool(np.any(a["return_ratio"] > 0)) and m > 1
    L = _Layout(m, T, returns)
    rows: list = []

    def link_end(start_col, i, t, per_unit):
        """(terms, const) of a message's end on link i in cell t."""
        if star:
            terms = [(start_col, 1.0), (L.g[i + 1, t], per_unit)]
        else:
            terms = [(start_col, 1.0)] + [(L.g[k, t], per_unit)
                                          for k in range(i + 1, m)]
        return terms, a["latency"][i]

    def comm_end(i, t):
        return link_end(L.cs[i, t], i, t, a["z"][i] * a["v_comm"][t])

    def ret_end(i, t):
        return link_end(L.rs[i, t], i, t, a["z"][i] * a["return_ratio"][t]
                        * a["v_comm"][t])

    def comp_end(i, t):
        return [(L.ps[i, t], 1.0),
                (L.g[i, t], a["w"][i] * a["v_comp"][t])], 0.0

    def after(col, end):
        """x[col] >= end  ->  end - x[col] <= 0."""
        terms, const = end
        rows.append((terms + [(col, -1.0)], const))

    def at_least(col, value):
        if value:
            rows.append(([(col, -1.0)], value))

    for t in range(T):
        rel = a["release"][t]
        for i in range(m - 1):
            if i == 0:
                at_least(L.cs[0, t], rel)
            if star:
                if i:
                    after(L.cs[i, t], comm_end(i - 1, t))
                elif t:
                    after(L.cs[0, t], comm_end(m - 2, t - 1))
            else:
                if i:
                    after(L.cs[i, t], comm_end(i - 1, t))
                if t:
                    after(L.cs[i, t], comm_end(i, t - 1))
                    if i + 1 <= m - 2:
                        after(L.cs[i, t], comm_end(i + 1, t - 1))
        for i in range(m):
            if t:
                after(L.ps[i, t], comp_end(i, t - 1))
            else:
                at_least(L.ps[i, 0], a["tau"][i])
            if i:
                after(L.ps[i, t], comm_end(i - 1, t))
            else:
                at_least(L.ps[0, t], rel)
        if returns:
            for i in range(m - 1):
                after(L.rs[i, t], comp_end(i + 1, t))
                if star:
                    if i:
                        after(L.rs[i, t], ret_end(i - 1, t))
                    elif t:
                        after(L.rs[0, t], ret_end(m - 2, t - 1))
                else:
                    if i + 1 <= m - 2:
                        after(L.rs[i, t], ret_end(i + 1, t))
                    if t:
                        after(L.rs[i, t], ret_end(i, t - 1))
                after(L.mk, ret_end(i, t))
    for i in range(m):
        after(L.mk, comp_end(i, T - 1))
    eq = [[(L.g[i, t], 1.0) for i in range(m)] for t in range(T)]
    return L, rows, eq


def _matrix(rows, n: int):
    cols, vals, ptr = [], [], [0]
    for terms, _ in rows:
        cols.extend(c for c, _ in terms)
        vals.extend(v for _, v in terms)
        ptr.append(len(cols))
    return sp.csr_matrix((vals, cols, ptr), shape=(len(rows), n))


def solve(p: dict) -> tuple:
    """(least makespan, its fractions [m, T]) by HiGHS."""
    L, rows, eq = _rows(p)
    c = np.zeros(L.n)
    c[L.mk] = 1.0
    res = linprog(
        c, A_ub=_matrix(rows, L.n), b_ub=np.array([-k for _, k in rows]),
        A_eq=_matrix([(r, 0.0) for r in eq], L.n), b_eq=np.ones(len(eq)),
        bounds=(0, None), method="highs")
    if res.status != 0:
        raise RuntimeError(f"reference LP: {res.message}")
    return float(res.fun), res.x[L.g].copy()
