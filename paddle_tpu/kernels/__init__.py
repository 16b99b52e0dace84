"""Pallas TPU kernel library — the Phi-fusion equivalent (SURVEY.md §2.1
"Phi fusion kernels", §7 phase 9): flash attention, fused rope, rmsnorm,
ring attention, paged-KV decode. On the CPU the kernels run in Pallas
interpret mode so the same tests run in CI without a TPU; on every other
platform they are compiled by Mosaic."""
import jax as _jax


def x64_off():
    """Context manager running its body with jax x64 disabled (pallas
    index maps / kernel constants must stay 32-bit; the package enables
    x64 globally for paddle int64 semantics)."""
    return _jax.enable_x64(False)


def interpret() -> bool:
    """The `interpret=` argument of every pallas_call in this package.
    Only the CPU emulates: it has no Mosaic. Any other platform compiles
    the kernel, so a backend Mosaic cannot target fails at lowering
    instead of running the emulator without a word."""
    return _jax.default_backend() == "cpu"

