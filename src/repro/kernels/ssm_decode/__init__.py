from repro.kernels.ssm_decode import ops, ref
