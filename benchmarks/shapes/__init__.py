"""Object shapes: what a deployment's tenants write, how convergence of
one write is recognised, and what every store must hold afterwards.

A shape is a module named in a configuration's ``shape`` key: a bare name
is a module of this package, a dotted one a module path. Generator
kinds and the comparison reach objects only through these functions, so
any traffic mix runs over any shape. Nothing here imports JAX: the load
generator's process imports shapes too.
"""

from __future__ import annotations

import importlib
import random


def load(name: str):
    return importlib.import_module(
        name if "." in name else f"benchmarks.shapes.{name}")


def tenant_names(n: int) -> list[str]:
    return [f"t{i:04d}" for i in range(n)]


def location_names(n: int) -> list[str]:
    return [f"loc{i}" for i in range(n)]


def seed_rng(seed: int, *salt: int) -> random.Random:
    """One reproducible stream per (seed, salt...); seeds above 2**31 are
    fine (Python integers, no fixed width anywhere)."""
    x = int(seed)
    for s in salt:
        x = x * 1_000_003 + int(s) + 0x9E3779B97F4A7C15
    return random.Random(x)


def population(shape, seed: int, n_tenants: int, per_tenant: int,
               locations: list[str]) -> dict[tuple[str, str], dict]:
    """The resident objects, a pure function of the seed: {(tenant, name):
    body}. The harness populates from it and the generator starts from it."""
    out: dict[tuple[str, str], dict] = {}
    for t, tenant in enumerate(tenant_names(n_tenants)):
        rng = seed_rng(seed, 1, t)
        for j in range(per_tenant):
            name = f"{shape.PREFIX}-{j:03d}-{rng.getrandbits(32):08x}"
            out[(tenant, name)] = shape.new(name, rng, locations)
    return out
