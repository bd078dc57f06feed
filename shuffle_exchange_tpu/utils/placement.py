"""Safe host→device placement for arrays that will be DONATED.

On the CPU backend, ``jax.device_put`` of an aligned numpy array can
zero-copy: the resulting jax.Array aliases the host buffer instead of
owning a copy. That alias is fine for read-only use, but an executable
with ``donate_argnums`` may reuse the buffer as scratch/output — and once
the numpy side is garbage-collected, the program is writing through freed
memory: silently corrupted training state, and eventually a segfault.

The resilience suite's bit-exact crash→resume cycles exposed this on the
checkpoint-restore path; master-init and the optimizer-offload swap-in
feed donated state from host numpy the same way. ``owned_device_put``
routes the host array through ``jnp.asarray`` first, which materializes
an XLA-owned buffer, so the subsequent reshard copies instead of
aliasing. On non-CPU backends host→device is always a real transfer, so
the extra hop is skipped.
"""

from __future__ import annotations


def owned_device_put(arr, sharding):
    """``jax.device_put`` whose result NEVER aliases host numpy memory —
    required for any array that lands in a donated (donate_argnums)
    pytree. No-op overhead off CPU."""
    import jax

    if jax.default_backend() == "cpu" and not isinstance(arr, jax.Array):
        import jax.numpy as jnp

        arr = jnp.asarray(arr)
    return jax.device_put(arr, sharding)


def cache_safe_donate_argnums(argnums):
    """``donate_argnums`` to pass to ``jax.jit``: the one seam every donating
    program of the package goes through (sxt-check rule SXT002 pins call
    sites to it).

    It used to drop donation on the CPU backend while the persistent
    compilation cache was on, for a runtime that freed donated inputs under
    a deserialized executable. On the installed jax (0.9.0) that race did
    not reproduce: with donation on, the resilience suite's bit-exact
    crash->resume cycles passed three times running on a warm cache
    (PR 23). Nothing is dropped now; if those cycles ever fail with
    garbage/NaN diffs, this is the place to look first. ROADMAP D1 retires
    the seam together with the rule."""
    return tuple(argnums)
