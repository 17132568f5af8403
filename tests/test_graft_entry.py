"""The hooks in __graft_entry__.py must stay runnable: entry() (single-chip
compile check) and dryrun_multichip(n) (full distributed step on a virtual
CPU mesh). A latent static-metadata mismatch in the dryrun's batch
construction once broke them without any suite test noticing (2026-07-31) —
pin both hooks here under the CPU-mesh conditions of conftest.py."""

import sys
import os

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft_entry  # noqa: E402


def test_entry_forward_jits():
    fn, args = graft_entry.entry()
    out = jax.jit(fn)(*args)
    for leaf in jax.tree.leaves(out):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_dryrun_multichip_8():
    # asserts internally (finiteness, metis unevenness); conftest provides
    # the 8 virtual CPU devices the driver's env would. The dryrun's 3D-mesh
    # tensor-parity leg is skipped here ONLY because tier-1 already runs it
    # as dedicated cases (test_tensor_parallel.py parity tests) — paying for
    # it twice would push the suite past its wall budget.
    graft_entry.dryrun_multichip(8, tensor_parity=False)
