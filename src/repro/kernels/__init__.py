"""Pallas TPU kernels for the compute hot-spots (validated with
interpret=True on CPU against the pure-jnp oracles in ref.py):

  flash_attention — blockwise attention fwd + dq/dkv bwd; band masks cover
                    full / causal / striped-causal (paper §3.7) / sliding
                    window; GQA via head-group index maps.
  ssd_scan        — Mamba-2 SSD chunked scan (state carried in VMEM).
  ops             — jit'd dispatch wrappers (pallas on TPU, ref elsewhere)
                    + the custom_vjp single-device flash_attention.
"""

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode follows the platform: compiled on a TPU, the
    interpreter elsewhere (``None``).  Asking for the interpreter on a TPU
    is refused, so a chip run never measures it."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode requested on a TPU")
    return bool(interpret)
