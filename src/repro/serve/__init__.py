"""Serving substrate: batched decode engine with continuous batching."""

from repro.serve.engine import Engine, Request

__all__ = ["Engine", "Request"]
