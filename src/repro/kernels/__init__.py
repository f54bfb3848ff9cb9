"""Pallas TPU kernels: the HFLEX streaming kernel (``sextans_spmm``), the
BSR tile kernel (``bsr_spmm``) and their XLA oracles (``ref``).  Callers
go through :mod:`repro.sparse_api`."""
