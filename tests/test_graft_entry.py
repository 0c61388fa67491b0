"""Driver-contract tests: entry() compiles, dryrun_multichip(8) runs, and
every console script of pyproject.toml resolves."""

import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, "/root/repo")

import __graft_entry__ as ge


def test_entry_jits_and_runs():
    fn, args = ge.entry()
    d, i = jax.jit(fn)(*args)
    jax.block_until_ready((d, i))
    assert d.shape == (8, 10) and i.shape == (8, 10)
    assert (np.asarray(i) >= 0).all()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_dryrun_multichip():
    ge.dryrun_multichip(8)


def _console_scripts() -> dict:
    import os
    import tomllib

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "pyproject.toml"), "rb") as f:
        return tomllib.load(f)["project"]["scripts"]


@pytest.mark.parametrize("target", sorted(_console_scripts().values()))
def test_console_script_resolves_to_a_callable(target):
    """Every ``[project.scripts]`` entry names a module that imports and
    a callable in it: a script whose target was deleted fails here, not
    on an operator's shell."""
    import importlib

    module, _, attr = target.partition(":")
    assert callable(getattr(importlib.import_module(module), attr))
