"""Plain float32 references of the benchmark's models, one module per
architecture, found by the ``reference`` key of a configuration file.

Each module defines ``init(key, cfg)`` (the parameters, from a PRNG key, in
the layout the program's model takes) and ``forward(params, x, cfg, bits=None)``
(the logits).  Every conv or dense site runs under :func:`common.site`,
named after the kernel pattern that serves it in the program, which is how
:mod:`chipbench.work` groups the work by kernel.  ``bits`` turns the
reference into the precision control: every site's activations (per
tensor) and weights (per output channel) rounded to that many bits.
"""
