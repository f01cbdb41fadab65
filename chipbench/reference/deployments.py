"""Per-station service demands of a deployment, from its knobs alone.

The plain reference's own lowering of a configuration file: the paper's
message counting (arXiv:2012.15762 sections 3-4; one message handled by
one server costs ``1 / alpha`` seconds), written out per protocol with no
import of the system under test.  Rows are laid out in the column order
the configuration file states (``station_columns``), so they line up with
the engines' canonical station slots.

Each formula keeps the order of operations of the paper's accounting, so
the float64 rows are the numbers the engines are fed, to the last bit.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def _multipaxos(f: int = 1, thrifty: bool = True
                ) -> List[Tuple[str, float, float]]:
    n = 2 * f + 1
    n_repl = n
    quorum = f + 1
    contacted = quorum if thrifty else n
    # leader: client request in, Phase 2a out, Phase 2b in, chosen out to
    # every replica, plus its replica role's share of the replies
    leader = 1 + contacted + quorum + n_repl + 1.0 / n_repl
    follower = 2.0 * contacted / n + 1 + 1.0 / n_repl
    # reads are ordered through the log like writes
    return [("leader", leader, leader), ("follower", follower, follower)]


def _compartmentalized(f: int = 1, n_proxy_leaders: int = 10,
                       grid_rows: int = 2, grid_cols: int = 2,
                       n_replicas: int = 4, batch_size: int = 1,
                       n_batchers: int = 0, n_unbatchers: int = 0
                       ) -> List[Tuple[str, float, float]]:
    r, w = grid_rows, grid_cols
    B = float(batch_size)
    col, row = r, w          # write quorum = a column, read quorum = a row
    out = []
    if n_batchers > 0:
        out.append(("batcher", (1 + 1 / B) / n_batchers,
                    (1 + (2 * row + 1) / B) / n_batchers))
        leader_w = 2.0 / B
    else:
        leader_w = 2.0
    out.append(("leader", leader_w, 0.0))
    proxies = max(n_proxy_leaders, 1)
    per_batch = 1 + col + col + n_replicas
    out.append(("proxy", per_batch / B / proxies, 0.0))
    out.append(("acceptor", 2.0 / w / B, 2.0 / r / B))
    reply = (1 / B) if n_unbatchers > 0 else 1.0
    out.append(("replica", 1.0 / B + reply / n_replicas,
                (1.0 / B + reply) / n_replicas))
    if n_unbatchers > 0:
        d = (1 / B + 1) / n_unbatchers
        out.append(("unbatcher", d, d))
    return out


def _unreplicated(batch_size: int = 1) -> List[Tuple[str, float, float]]:
    d = 2.0 / float(batch_size)
    return [("server", d, d)]


_TABLES = {"multipaxos": _multipaxos,
           "compartmentalized": _compartmentalized,
           "unreplicated": _unreplicated}

# variants with no read path: every command, read or write, goes through
# the log as a write (paper section 3)
READS_AS_WRITES = frozenset({"multipaxos"})


def station_table(variant: str, knobs: Dict) -> List[Tuple[str, float, float]]:
    """(station, write demand, read demand) per server, in messages."""
    if variant not in _TABLES:
        raise ValueError(f"no reference demand table for variant {variant!r}")
    return _TABLES[variant](**knobs)


def demand_rows(deployments: Sequence[Dict], columns: Sequence[str]
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Write and read demand rows [M, K] (messages per command per server)
    of a configuration file's ``deployments``, in ``columns`` order."""
    index = {name: k for k, name in enumerate(columns)}
    d_w = np.zeros((len(deployments), len(columns)))
    d_r = np.zeros_like(d_w)
    for i, dep in enumerate(deployments):
        for station, w, r in station_table(dep["variant"], dep["knobs"]):
            d_w[i, index[station]] += w
            d_r[i, index[station]] += r
    return d_w, d_r


def alpha(anchor: Dict) -> float:
    """Messages per second per server that put the anchor deployment's
    bottleneck at ``anchor['cmd_per_s']`` (paper: MultiPaxos, 25k cmd/s)."""
    table = station_table(anchor["variant"], anchor["knobs"])
    return anchor["cmd_per_s"] * max(w for _, w, _ in table)


def blend(d_w: np.ndarray, d_r: np.ndarray, f_write: float) -> np.ndarray:
    """Effective demand at a write fraction."""
    return f_write * d_w + (1.0 - f_write) * d_r
