"""Counter backends beside the in-graph events (paper C6: reuse Perfmon/PAPI;
ours reuse what the JAX/XLA stack exposes).

* ``ingraph``   — event values computed inside the XLA program on live
                  tensors (implemented in core/instrument.py + core/events.py).
* ``host_time`` — named series of host wall-clock samples and their
                  outliers (``fit``'s per-step straggler tripwire).
* ``hlo_graph`` — FLOPs, HBM bytes and collective bytes of a compiled
                  module, parsed from its HLO text with loop trip counts.
"""
