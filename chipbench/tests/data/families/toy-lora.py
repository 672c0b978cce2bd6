"""A family for the CPU tests whose problem is an adapter on a frozen base.
The program has no token family, so its problem here only holds what the
program would be given: the adapter's start and the frozen weights."""
from __future__ import annotations

import types


def build_problem(config: dict, data: dict, x0, frozen):
    del config
    return types.SimpleNamespace(data=data, x0=x0, frozen=frozen)
