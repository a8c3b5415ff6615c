"""Control: an undirected view walked along the loaded direction only,
breaking the guarantee that every edge is traversable both ways.

Spec (a configuration's ``control``): ``{"break": "one_direction"}``.
"""
from __future__ import annotations

import dataclasses

from bench.graphdata import Deployment


def broken(dep: Deployment, spec) -> Deployment:
    return dataclasses.replace(dep, directed=True)
