"""Shared obs wiring for the launch CLIs.

Every driver (`launch.train`, `launch.serve`, `launch.dryrun`) takes the
same three flags:

  --trace PATH        write the span/event/audit stream as JSONL
  --metrics-out PATH  write the metrics-registry dump on exit
  --jax-profile DIR   capture a jax.profiler trace into DIR

Passing either of the first two opens the module-level obs session; with
neither, the session stays closed and every hook in the executors is a
no-op (the zero-overhead default).  ``--jax-profile`` needs no session:
the ``obs.scope`` spans reach the profiler trace on their own.
"""

from __future__ import annotations

import contextlib

import jax

from repro import obs


def add_obs_args(ap) -> None:
    ap.add_argument("--trace", default="",
                    help="write a schema-versioned JSONL span/event trace "
                         "(rows, transfers, scheduler ticks, plan audits) "
                         "to this path")
    ap.add_argument("--metrics-out", default="",
                    help="write the metrics-registry dump (counters / "
                         "gauges / histogram summaries) to this path on "
                         "exit")
    ap.add_argument("--jax-profile", default="",
                    help="capture a jax.profiler trace into this "
                         "directory; with --trace, its obs_anchor span "
                         "maps the JSONL's t_ns onto the profile's clock")


def configure_from_args(args, **meta) -> bool:
    """Open an obs session if the CLI asked for one.  Returns enabled."""
    if not (args.trace or args.metrics_out):
        return False
    obs.configure(trace=args.trace or None,
                  metrics=args.metrics_out or None, meta=meta)
    return True


@contextlib.contextmanager
def profiled(args):
    """jax.profiler capture scoped over the run when --jax-profile is set.

    The capture opens with one ``obs_anchor`` span, written into the
    profile and, with a session open, into the JSONL trace: the profile's
    host events are timed from the capture's start, so the anchor's
    ``t_ns`` minus its start in the profile maps every JSONL ``t_ns``
    onto the profile's timeline."""
    active = bool(getattr(args, "jax_profile", ""))
    if active:
        jax.profiler.start_trace(args.jax_profile)
        with obs.scope("obs_anchor"):
            pass
    try:
        yield
    finally:
        if active:
            jax.profiler.stop_trace()
