"""Minimal host-side logging with process-0 gating.

The reference logs via bare ``print`` gated on rank 0
(reference train/distributed_trainer.py:201-212, SURVEY.md §5.5). Here the
process identity comes from ``jax.process_index()`` instead of RANK env vars.
"""

from __future__ import annotations

import logging
import sys

import jax

_CONFIGURED = False


def get_logger(name: str = "pdtpu") -> logging.Logger:
    global _CONFIGURED
    logger = logging.getLogger(name)
    if not _CONFIGURED:
        handler = logging.StreamHandler(sys.stdout)
        handler.setFormatter(
            logging.Formatter("[%(asctime)s %(name)s] %(message)s", "%H:%M:%S")
        )
        root = logging.getLogger("pdtpu")
        root.addHandler(handler)
        root.setLevel(logging.INFO)
        root.propagate = False
        _CONFIGURED = True
    return logger


def log_event(
    event: str, *, logger: logging.Logger | None = None, **fields
) -> None:
    """One structured lifecycle line: ``event=<name> key=value ...`` with
    keys sorted and Nones dropped, so serving-engine incidents (storm
    failures, chaos runs) are diagnosable — and greppable — from the log
    alone. Emitted at DEBUG on the ``pdtpu.serving`` child logger:
    lifecycle events are per-request bookkeeping, not operator output;
    enable with ``get_logger("pdtpu.serving").setLevel(logging.DEBUG)``.
    Host-side only — never call
    from traced code (repolint's host-sync rule would flag the formatting
    anyway)."""
    lg = logger or get_logger("pdtpu.serving")
    if lg.isEnabledFor(logging.DEBUG):
        parts = [f"event={event}"] + [
            f"{k}={fields[k]}"
            for k in sorted(fields)
            if fields[k] is not None
        ]
        lg.debug(" ".join(parts))


def is_process_zero() -> bool:
    return jax.process_index() == 0


def log_on_process_zero(message: str, logger: logging.Logger | None = None) -> None:
    if is_process_zero():
        (logger or get_logger()).info(message)
