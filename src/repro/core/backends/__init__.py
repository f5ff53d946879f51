"""Pluggable counter backends (paper C6: reuse Perfmon/PAPI; ours reuse what
the JAX/XLA stack exposes).

* ``ingraph``   — event values computed inside the XLA program on live
                  tensors (implemented in core/instrument.py + core/events.py;
                  this package re-exports helpers).
* ``xla_cost``  — static per-program and per-scope FLOPs / bytes / collective
                  traffic from the compiled artifact (roofline source).
* ``host_time`` — named series of host wall-clock samples and their
                  outliers (``fit``'s per-step straggler tripwire).
* ``host_callback`` — a deliberately perfmon-like backend: an ``io_callback``
                  host round-trip on every scope entry/exit (the breakpoint
                  analogue).  Exists to reproduce the paper's overhead
                  hierarchy; do not use it in production.
"""
from . import host_callback, host_time, xla_cost  # noqa: F401
