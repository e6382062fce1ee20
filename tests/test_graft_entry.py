"""The driver's contract: ``entry()`` compile-checks single-chip and
``dryrun_multichip(n)`` executes a sharded train step on an n-device mesh.
Under conftest's virtual 8-CPU topology both run without TPU hardware."""

import jax
import pytest

from __graft_entry__ import _layout, dryrun_multichip, entry


def test_entry_compiles_and_runs():
    fn, args = entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    params, tokens = args
    assert out.shape == (*tokens.shape, 4096)  # [B, S, V]


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_layout_factors_device_count(n):
    layout = _layout(n)
    assert layout.dp * layout.sp * layout.ep * layout.tp == n


def test_dryrun_multichip_8():
    dryrun_multichip(8)
