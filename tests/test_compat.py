"""repro.compat — the seam around JAX's less stable APIs.

Two halves:
  * unit tests that every seam (shard_map / use_mesh / mosaic_params /
    jit_sharded / the interpret-mode choice) WORKS on the installed JAX;
  * a source-scan regression test enforcing the seam's one rule: nothing
    under src/repro/ outside compat/ (and nothing under tools/) may
    reference the version-sensitive spellings directly.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- resolution --------------------------------------------------------------


def test_describe_reports_every_seam():
    d = compat.describe()
    assert d["jax_version"] == jax.__version__
    assert d["backend"] == jax.default_backend()
    assert d["kernel_path"] == compat.kernel_path()


def test_shard_map_resolves_and_runs():
    mesh = jax.make_mesh((1,), ("x",))
    f = compat.shard_map(lambda a: a * 2.0, mesh, in_specs=P(),
                         out_specs=P())
    np.testing.assert_allclose(np.asarray(f(jnp.ones(4))), 2.0)


def test_shard_map_accepts_check_vma_spelling():
    """check_vma must be translated to whatever this JAX calls it."""
    mesh = jax.make_mesh((1,), ("x",))
    for flag in (False, True):
        f = compat.shard_map(lambda a: a + 1.0, mesh, in_specs=P(),
                             out_specs=P(), check_vma=flag)
        np.testing.assert_allclose(np.asarray(f(jnp.zeros(2))), 1.0)


def test_use_mesh_context_manager():
    mesh = jax.make_mesh((1,), ("x",))
    with compat.use_mesh(mesh) as m:
        assert m is mesh
        # re-entrancy: nested contexts must not blow up
        with compat.use_mesh(mesh):
            pass


def test_use_mesh_enables_sharded_jit():
    mesh = jax.make_mesh((1,), ("x",))
    sh = NamedSharding(mesh, P("x"))
    with compat.use_mesh(mesh):
        out = jax.jit(lambda a: a * 3.0, in_shardings=sh,
                      out_shardings=sh)(jnp.ones(8))
    np.testing.assert_allclose(np.asarray(out), 3.0)


def test_mosaic_params_resolves_on_installed_jax():
    got = compat.mosaic_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert set(got) == {"compiler_params"}
    assert type(got["compiler_params"]).__name__ == "CompilerParams"
    assert got["compiler_params"].dimension_semantics == ("parallel",
                                                          "arbitrary")


def test_mosaic_params_accepted_by_pallas_call():
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=True,
        **compat.mosaic_params(dimension_semantics=()),
    )(jnp.ones((8, 128), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 2.0)


# -- capability probes -------------------------------------------------------


def test_capability_probes_are_consistent(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    on_cpu = jax.default_backend() == "cpu"
    assert compat.pallas_interpret_default() is on_cpu
    assert compat.kernel_path() == ("pallas_interpret" if on_cpu
                                    else "mosaic")


def test_failed_device_query_is_not_read_as_cpu(monkeypatch):
    """A backend that cannot be queried must raise, never fall back to
    interpret mode (which would run every kernel in Python, silently)."""
    from repro.compat import capability

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(capability.jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        compat.resolve_interpret(None)
    assert compat.resolve_interpret(True) is True


def test_resolve_interpret_tristate():
    assert compat.resolve_interpret(True) is True
    assert compat.resolve_interpret(False) is False
    assert compat.resolve_interpret(None) == compat.pallas_interpret_default()


def test_pallas_interpret_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert compat.pallas_interpret_default() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert compat.pallas_interpret_default() is False


# -- jit over PartitionSpec pytrees ------------------------------------------


def test_resolve_shardings_binds_specs_and_keeps_none():
    mesh = jax.make_mesh((1,), ("x",))
    tree = ({"w": P("x"), "b": P()}, None)
    got = compat.resolve_shardings(mesh, tree)
    assert isinstance(got[0]["w"], NamedSharding)
    assert got[0]["w"].spec == P("x")
    assert got[1] is None
    already = NamedSharding(mesh, P())
    assert compat.resolve_shardings(mesh, already) is already


def test_jit_sharded_runs_with_spec_pytrees():
    mesh = jax.make_mesh((1,), ("x",))

    def step(params, batch):
        return {"w": params["w"] + batch.sum()}, None

    with compat.use_mesh(mesh):
        jf = compat.jit_sharded(step, mesh,
                                in_shardings=({"w": P()}, P("x")),
                                out_shardings=({"w": P()}, None))
        out, _ = jf({"w": jnp.zeros(3)}, jnp.ones(4))
    np.testing.assert_allclose(np.asarray(out["w"]), 4.0)


# -- source-scan regression --------------------------------------------------

# every documented spelling of the version-sensitive APIs, old and new:
# shard_map (both locations), the mesh context (both spellings), and the
# Pallas compiler-params classes
BANNED = re.compile(r"jax\.shard_map|jax\.experimental\.shard_map"
                    r"|set_mesh|jax\.sharding\.use_mesh|CompilerParams")


def _scan(root, skip_dir=None):
    hits = []
    for dirpath, _dirnames, filenames in os.walk(root):
        if skip_dir and os.path.abspath(dirpath).startswith(skip_dir):
            continue
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if BANNED.search(line):
                        hits.append(f"{os.path.relpath(path, ROOT)}:"
                                    f"{lineno}: {line.strip()}")
    return hits


def test_no_direct_version_sensitive_jax_apis_outside_compat():
    """repro.compat is the single entry point for version-sensitive JAX
    APIs; any direct reference elsewhere re-litters the tree with the
    exact churn this layer exists to absorb."""
    hits = _scan(os.path.join(ROOT, "src", "repro"),
                 skip_dir=os.path.join(ROOT, "src", "repro", "compat"))
    hits += _scan(os.path.join(ROOT, "tools"))
    assert not hits, ("direct version-sensitive JAX API use — route through "
                      "repro.compat:\n" + "\n".join(hits))
