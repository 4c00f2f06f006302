"""Named scopes for the profiler.

``@scoped(name)`` runs a function under ``jax.named_scope(name)``, so
every operation it stages carries ``name`` in its op name (the HLO
``op_name`` metadata the profiler's trace shows).  The scope is looked
up on each call and is metadata only: it changes no compiled operation.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax


def scoped(name: str) -> Callable[[Callable], Callable]:
    def deco(f: Callable) -> Callable:
        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return f(*args, **kwargs)
        return wrapper
    return deco
