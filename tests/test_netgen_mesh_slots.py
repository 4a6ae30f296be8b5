"""The mesh context and slot helpers that netgen's serving path uses
(`repro.netgen.mesh`, `repro.netgen.slots`)."""
import threading

import jax
import numpy as np
import pytest

from repro.netgen.mesh import (
    active_mesh, make_host_mesh, make_mesh_compat, use_mesh,
)
from repro.netgen.slots import pad_slots, stack_requests


def test_use_mesh_nests_and_restores_active_mesh():
    outer = make_host_mesh(data=1)
    inner = make_mesh_compat((1,), ("model",))
    assert active_mesh() is None
    with use_mesh(outer):
        assert active_mesh() is outer
        with use_mesh(inner):
            assert active_mesh() is inner
        assert active_mesh() is outer
        with use_mesh(None):
            assert active_mesh() is None
        assert active_mesh() is outer
    assert active_mesh() is None


def test_use_mesh_restores_after_an_exception():
    mesh = make_host_mesh(data=1)
    with pytest.raises(RuntimeError, match="boom"):
        with use_mesh(mesh):
            raise RuntimeError("boom")
    assert active_mesh() is None
    with use_mesh(mesh):
        with pytest.raises(ValueError):
            with use_mesh(make_mesh_compat((1,), ("model",))):
                raise ValueError
        assert active_mesh() is mesh


def test_active_mesh_is_none_on_a_fresh_thread():
    seen = []
    with use_mesh(make_host_mesh(data=1)):
        t = threading.Thread(target=lambda: seen.append(active_mesh()))
        t.start()
        t.join()
        assert active_mesh() is not None
    assert seen == [None]


def test_make_host_mesh_axes_and_clamped_sizes():
    mesh = make_host_mesh(data=1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] == 1 and mesh.shape["model"] == 1
    # asking for more devices than exist clamps to what the host has
    big = make_host_mesh(data=10_000)
    assert big.shape["data"] == len(jax.devices())
    assert big.shape["model"] == 1


def test_stack_requests_stacks_rows_in_order():
    rows = [np.full(5, i, dtype=np.uint8) for i in range(4)]
    batch = stack_requests(rows)
    assert batch.shape == (4, 5) and batch.dtype == np.uint8
    np.testing.assert_array_equal(batch[:, 0], [0, 1, 2, 3])


@pytest.mark.parametrize("rows", [
    [np.zeros(3, np.uint8), np.zeros(4, np.uint8)],
    [np.zeros(3, np.uint8), np.zeros(3, np.int32)],
    [],
])
def test_stack_requests_refuses_mixed_or_empty(rows):
    with pytest.raises(ValueError):
        stack_requests(rows)


def test_pad_slots_pads_with_zero_rows_and_refuses_overflow():
    x = np.arange(6, dtype=np.uint8).reshape(2, 3) + 1
    block, n = pad_slots(x, 4)
    assert n == 2 and block.shape == (4, 3) and block.dtype == np.uint8
    np.testing.assert_array_equal(block[:2], x)
    assert not block[2:].any()
    full, n = pad_slots(x, 2)
    assert n == 2 and full is x
    with pytest.raises(ValueError, match="exceeds slot capacity"):
        pad_slots(x, 1)
