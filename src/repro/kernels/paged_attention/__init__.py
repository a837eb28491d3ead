from repro.kernels.paged_attention import ops, ref
