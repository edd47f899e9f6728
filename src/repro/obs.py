"""Profiler spans around the store's stages.

``span(name, **args)`` marks one stage of one wave on the host plane of a
``jax.profiler`` trace (``TraceAnnotation``), on the same clock as the
device's operations, so a trace shows which stage the host was in while
the device waited.  Spans open once per wave, commit wave or gather, never
per request, tile or row.

A span that carries ``wave=`` or ``commit_wave=`` passes that id on to
every span opened inside it on the same thread, so the stages of one wave
share an id: ``serve.flush(wave=7)`` > ``serve.dispatch`` >
``checkout.plan`` all carry ``wave=7``, while the previous wave's
``serve.deliver(wave=6)`` inside the same flush carries its own.

This module imports nothing heavy: a process that never loaded ``jax``
gets one shared no-op (``import repro.core`` loads no ``jax``, and a span
cannot be recorded without it).  The stage counters live on the objects
that do the work (``core.checkout.WaveStages``,
``core.partition.IngestWaveReport``) and are summed into
``serve.checkout.CheckoutStats``; spans only mark time.
"""
from __future__ import annotations

import contextlib
import contextvars
import sys

# every span the program opens, outermost layer first
SPAN_NAMES = (
    "serve.flush",            # one flush: land writes, dispatch, deliver
    "serve.dispatch",         # route, plan, pin and launch one read wave
    "serve.deliver",          # join one read wave and hand out its blocks
    "checkout.plan",          # plan_wave_cached, per gather
    "checkout.launch",        # the jitted gather call: trace, compile, enqueue
    "checkout.pin",           # pin a superblock: host build, evictions, upload
    "checkout.stragglers",    # a group wave's per-partition straggler batch
    "checkout.device_wait",   # block until one packed gather is computed
    "checkout.d2h",           # copy one packed gather to the host and split it
    "ingest.commit_many",     # one commit wave, end to end
    "ingest.stage",           # delta extraction, CSR/data concat, rebuilds
    "ingest.merge",           # one merge's match against its parents' union
    "journal.append",         # encode, write and (sync) fsync one record
    "journal.fsync",          # the fsync of a synced record
    "ingest.refresh",         # post-commit superblock extension and upload
)
ID_KEYS = ("wave", "commit_wave")

_NOOP = contextlib.nullcontext()
_ids: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "repro_span_ids", default={})


class _Span:
    __slots__ = ("_ann", "_ids", "_token")

    def __init__(self, annotation, name: str, args: dict):
        ids = _ids.get()
        own = {k: args[k] for k in ID_KEYS if k in args}
        self._ids = {**ids, **own} if own else None
        self._ann = annotation(name, **{**ids, **args})

    def __enter__(self):
        if self._ids is not None:
            self._token = _ids.set(self._ids)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._ids is not None:
            _ids.reset(self._token)
        return False


def span(name: str, **args):
    """A context manager marking stage ``name`` (one of ``SPAN_NAMES``) with
    ``args`` (ids, byte and tile counts) on the profiler's host plane; the
    shared no-op when ``jax`` was never imported."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NOOP
    return _Span(jax.profiler.TraceAnnotation, name, args)
