"""Ambient mesh context for model code that needs explicit collectives
(shard_map MoE). Set by step factories / engines before tracing."""
from __future__ import annotations

from contextlib import contextmanager

_MESH = None


def set_mesh(mesh) -> None:
    global _MESH
    _MESH = mesh


def get_mesh():
    return _MESH


@contextmanager
def mesh_context(mesh):
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        yield
    finally:
        _MESH = prev
