"""Fixture: jax-compat positive — an API the installed jax removed, in
all three spellings the rule must catch. Not a test module; linted by
tests/test_tpu_lint.py."""
import jax


def kernel_entry(x):
    with jax.experimental.enable_x64(False):  # removed in jax 0.9.0
        return x


def silent_fallback(x, pallas, xla):
    # the dead-kernel-library bug verbatim: a catch-everything handler is
    # NOT a feature-detection probe — the kernel path dies silently
    try:
        with jax.experimental.enable_x64(False):
            return pallas(x)
    except Exception:
        return xla(x)


def probe(x):
    # this IS the feature-detection idiom: exempt
    try:
        ctx = jax.experimental.enable_x64
    except AttributeError:
        ctx = jax.enable_x64
    return ctx


def from_import_spelling():
    from jax.experimental import enable_x64  # same API, ImportError
    return enable_x64
