"""Driver entry-point regression tests: ``entry()`` jits on one device and
``dryrun_multichip(8)`` runs on the 8 virtual CPU devices conftest sets up.
"""

import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__ as graft  # noqa: E402


def test_entry_jits_single_chip():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)


def test_dryrun_multichip_8_in_process():
    graft.dryrun_multichip(8)


def test_dryrun_multichip_raises_for_want_of_devices():
    with pytest.raises(RuntimeError, match="need 64 devices, have 8"):
        graft.dryrun_multichip(64)
