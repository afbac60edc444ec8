"""Work of the algorithm, counted from the configuration's shapes: each
kernel's operations and bytes (a model's FLOPs per token are its family's,
``chipbench.families``).
A kernel's roofline share divides the least time this work could take by
the kernel's device time, so a change that fuses or replaces a kernel is
judged against the same work."""
