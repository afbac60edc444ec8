"""Work of the algorithm, counted from the configuration's shapes: the
model FLOPs of a training step and each kernel's operations and bytes.
A kernel's roofline share divides the least time this work could take by
the kernel's device time, so a change that fuses or replaces a kernel is
judged against the same work."""
