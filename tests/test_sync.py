"""Handle-semantics surface: synchronize / poll / barrier / hard_sync.

Reference anchor: ``bf.synchronize(handle)`` / ``bf.poll(handle)`` / the
handle manager (`/root/reference/bluefog/torch/mpi_ops.py:962-1005`).  JAX
arrays are the handles; ``hard_sync`` is the device-to-host barrier the
timing loops close with (see bf.hard_sync docstring).
"""
import jax
import jax.numpy as jnp
import numpy as np

import bluefog_tpu as bf


def test_synchronize_returns_value():
    x = jnp.arange(4.0)
    y = bf.synchronize(x * 2)
    np.testing.assert_allclose(np.asarray(y), [0, 2, 4, 6])


def test_poll_true_after_synchronize():
    x = jnp.arange(4.0) + 1
    bf.synchronize(x)
    assert bf.poll(x) is True


def test_barrier_runs():
    bf.barrier()


def test_hard_sync_passes_through_pytrees():
    tree = {"a": jnp.ones((3, 2)), "b": (jnp.zeros(()), [1.5, None])}
    out = bf.hard_sync(tree)
    assert out is tree
    np.testing.assert_allclose(np.asarray(out["a"]), np.ones((3, 2)))


def test_hard_sync_empty_and_scalar():
    assert bf.hard_sync(()) == ()
    s = jnp.float32(3.0)
    assert float(bf.hard_sync(s)) == 3.0
