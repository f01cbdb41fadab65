"""Per-station service demands of deployments whose 2f+1 acceptors use
majority quorums: the paper's Fig. 29a ablation before its grid.

The paper's message counting (arXiv:2012.15762 sections 3-4 and 8.2) for
compartmentalized MultiPaxos with ``quorums="majority"``: a proxy leader
sends Phase 2a to a thrifty f+1 of the 2f+1 acceptors and counts their
f+1 Phase 2b replies, so each acceptor handles 2 messages for f+1 of
every 2f+1 writes; a read's preread goes to f+1 acceptors the same way.
Rows of every other deployment are ``deployments.station_table``'s.  No
import of the system under test; the order of operations is that of the
paper's accounting, as in ``deployments``.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from chipbench.reference import deployments


def _majority(f: int = 1, n_proxy_leaders: int = 10, grid_rows: int = 3,
              grid_cols: int = 1, n_replicas: int = 4, batch_size: int = 1,
              n_batchers: int = 0, n_unbatchers: int = 0,
              quorums: str = "majority") -> List[Tuple[str, float, float]]:
    n_acc = 2 * f + 1
    if quorums != "majority" or (grid_rows, grid_cols) != (n_acc, 1):
        raise ValueError("majority quorums span the (2f+1, 1) column")
    B = float(batch_size)
    q = f + 1                # a thrifty majority, for writes and reads
    out = []
    if n_batchers > 0:
        out.append(("batcher", (1 + 1 / B) / n_batchers,
                    (1 + (2 * q + 1) / B) / n_batchers))
        leader_w = 2.0 / B
    else:
        leader_w = 2.0
    out.append(("leader", leader_w, 0.0))
    proxies = max(n_proxy_leaders, 1)
    per_batch = 1 + q + q + n_replicas
    out.append(("proxy", per_batch / B / proxies, 0.0))
    acc = 2.0 * q / n_acc / B
    out.append(("acceptor", acc, acc))
    reply = (1 / B) if n_unbatchers > 0 else 1.0
    out.append(("replica", 1.0 / B + reply / n_replicas,
                (1.0 / B + reply) / n_replicas))
    if n_unbatchers > 0:
        d = (1 / B + 1) / n_unbatchers
        out.append(("unbatcher", d, d))
    return out


def station_table(variant: str, knobs: Dict) -> List[Tuple[str, float, float]]:
    """(station, write demand, read demand) per server, in messages."""
    if variant == "compartmentalized" and knobs.get("quorums") == "majority":
        return _majority(**knobs)
    return deployments.station_table(variant, knobs)


def demand_rows(rows: Sequence[Dict], columns: Sequence[str]
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Write and read demand rows [M, K] (messages per command per server)
    of a configuration file's ``deployments``, in ``columns`` order."""
    index = {name: k for k, name in enumerate(columns)}
    d_w = np.zeros((len(rows), len(columns)))
    d_r = np.zeros_like(d_w)
    for i, dep in enumerate(rows):
        for station, w, r in station_table(dep["variant"], dep["knobs"]):
            d_w[i, index[station]] += w
            d_r[i, index[station]] += r
    return d_w, d_r
