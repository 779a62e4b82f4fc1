"""Work counts: the operations and bytes each kernel's step needs.

One module per kernel. Each counts from the work the algorithm needs, at
the configuration's dtype (bf16: 2 bytes), from the live lengths the
harness reads off the engine's requests after every ``step()`` — never
from how today's kernel does it (its f32 staging, padded grids or
one-hot matmuls). A kernel that does the needed work in less time can
then never read over 100 % of its roofline.

Each module gives ``count(w, step) -> (flops, bytes)``, where ``w`` is a
``discover.Widths`` and ``step`` a ``driver.StepWork``, and ``MATCH``, the
substrings that name the kernel's events in a device trace.
"""
