"""Control: a snapshot that lags the load. The last ``share`` of the loaded
edge rows are invisible, breaking the guarantee that every loaded edge is
visible to every query (a replica behind its primary).

Spec (a configuration's ``control``): ``{"break": "stale_rows", "share": s}``.
"""
from __future__ import annotations

import dataclasses

from bench.graphdata import Deployment


def broken(dep: Deployment, spec) -> Deployment:
    keep = dep.n_edges - int(round(float(spec["share"]) * dep.n_edges))
    return dataclasses.replace(dep, edge={k: v[:keep] for k, v in dep.edge.items()})
