"""Kernels and step: share of the decode program's own device time under the
scopes that hold its matmuls — qkv, o_proj, mlp and head (their biases,
residual adds and activations with them), %."""
from benchmark import span_reduce


def read(rec):
    return span_reduce.scope_share(
        rec, (span_reduce.DECODE_MODULE,), ("qkv", "o_proj", "mlp", "head"))
