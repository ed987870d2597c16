"""Driver contract: entry() compile-check and dryrun_multichip on CPU mesh."""

import os
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import __graft_entry__  # noqa: E402


def test_entry_jits_and_runs():
    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    out.block_until_ready()
    assert out.shape == args[0].shape and out.dtype == args[0].dtype


@pytest.mark.slow  # minutes-scale interpret-mode sweep; run with -m slow
def test_dryrun_multichip_8():
    __graft_entry__.dryrun_multichip(8)


@pytest.mark.slow  # minutes-scale interpret-mode sweep; run with -m slow
def test_dryrun_multichip_odd_counts():
    for n in (1, 2, 3, 6):
        __graft_entry__.dryrun_multichip(n)


def test_dryrun_multichip_refuses_missing_devices():
    """No virtual-CPU stand-in: asking for more devices than exist raises."""
    with pytest.raises(RuntimeError, match="needs 16 devices"):
        __graft_entry__.dryrun_multichip(16)
