"""Means of the program's own spans over a run's micro-batches or delta
publishes, for the readers in ``bench/metrics/``.

``run.spans`` holds ``(name, t0, t1)`` for every span the server's tracer
kept. A traced server records each micro-batch (``coalesce``, ``begin``,
``finish`` and the engine's and hybrid store's steps under them) and each
delta publish (``publish_delta`` and its ``publish.*`` steps) once, and a
sampled request's chain repeats its batch's spans with the same times, so
a span counts once per distinct ``(name, t0, t1)``. Only spans that start
in the window (at or after ``run.t0``) count, so warm-up is left out.

A program that records no span of the name gives None: the metric is left
out of the result line.
"""
from __future__ import annotations


def distinct(run, name: str) -> set:
    """The ``(t0, t1)`` of each distinct span ``name`` in the window."""
    return {(t0, t1) for n, t0, t1 in run.spans or ()
            if n == name and t0 >= run.t0}


def per_unit_ms(run, name: str, unit: str) -> float | None:
    """Summed duration of the spans ``name``, in ms, over the number of
    spans ``unit``: each batch has one ``finish``, each publish one
    ``publish_delta``."""
    spans = distinct(run, name)
    n = len(distinct(run, unit))
    if not spans or not n:
        return None
    return sum(t1 - t0 for t0, t1 in spans) / n * 1e3


def per_batch_ms(run, name: str) -> float | None:
    return per_unit_ms(run, name, "finish")


def per_publish_ms(run, name: str) -> float | None:
    return per_unit_ms(run, name, "publish_delta")
