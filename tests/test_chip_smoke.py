"""chip_smoke.py off the chip: it must refuse to pass, and its phases must run.

The script's contract is that ``"ok": true`` is printed only on a TPU with
every phase green.  Here, on the CPU pod: the script as the driver runs it
fails without the ok line; the phases — plain functions of their sizes — run
small and green when called directly; the chip-only checks (a flash kernel in
the compiled step, Mosaic at every Pallas site) refuse what the CPU produced;
and the two helpers a chip run leans on (the compile-cache placement, the
peak table) behave as stated.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TINY = dict(layers=2, heads=2, dmodel=64, seq=64, vocab=512)


@pytest.fixture
def mesh4():
    from adapcc_tpu.comm.mesh import build_world_mesh

    return build_world_mesh(4)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["one-chip", "four-chips"])
def test_script_fails_without_a_chip(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr and "'cpu'" in out.stderr


def test_one_chip_phases_run_on_the_cpu_pod(tmp_path):
    from adapcc_tpu.comm.mesh import build_world_mesh

    chip_smoke.phase_bootstrap(build_world_mesh(1), str(tmp_path))
    assert (tmp_path / "logical_graph.xml").exists()  # artifacts land in the work dir
    report = chip_smoke.phase_train(1, 4, 4, 1, TINY)
    assert len(report["losses"]) == 4 and report["losses"][-1] < report["losses"][0]
    assert report["compile"]["programs"] > 0 and report["compile"]["seconds"] > 0
    # the CPU inlined the interpreter: no kernel in the step, and the check
    # a chip run ends on says so instead of passing
    assert report["custom_calls"] == 0
    with pytest.raises(AssertionError, match="ran the Pallas interpreter"):
        chip_smoke.assert_kernels_not_interpreted(["flash_attention"])


def test_main_prints_no_ok_when_the_step_holds_no_flash_kernel(monkeypatch, capsys):
    """With the device check stubbed, the one-chip run reaches its end on the
    CPU — and still refuses: an interpreted step is not a pass."""
    monkeypatch.setattr(
        chip_smoke, "require_tpu",
        lambda chips: {"platform": "tpu", "kind": "stub", "count": 1},
    )
    monkeypatch.setattr(chip_smoke, "GPT2_SMALL", TINY)
    monkeypatch.setattr(
        "adapcc_tpu.utils.compile_cache.enable_compile_cache", lambda: "(stubbed)"
    )
    with pytest.raises(AssertionError, match="holds no flash kernel"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_executors_place_four_distinct_shards(mesh4):
    ran = chip_smoke.phase_executors(mesh4, 8 * 1024)
    assert ran["xla"] == "xla" and ran["ring"] == "schedule"
    assert {ran["tree"], ran["rd"], ran["ir"]} == {"tree", "rd", "ir"}
    assert ran["pallas_ring+int8"] == "pallas_ring[vmem+int8]"
    # off the chip the dispatch trace admits the interpreter ran
    assert ran["interpreted"] == sorted(
        v for k, v in ran.items() if k.startswith("pallas_ring")
    )


def test_sharding_check_refuses_one_device(mesh4):
    from jax.sharding import NamedSharding, PartitionSpec as P

    spread = jax.device_put(jnp.zeros((4, 8)), NamedSharding(mesh4, P("ranks")))
    chip_smoke.assert_sharded_over(spread, mesh4, "spread")
    lumped = jax.device_put(jnp.zeros((4, 8)), jax.devices()[0])
    with pytest.raises(AssertionError, match="1 shards on 1 devices"):
        chip_smoke.assert_sharded_over(lumped, mesh4, "lumped")


def test_four_chip_ddp_matches_plain_psum(mesh4):
    losses = chip_smoke.phase_ddp_vs_psum(mesh4, 2, 2, TINY)
    assert len(losses["ddp"]) == 2 and losses["ddp"][1] < losses["ddp"][0]


def test_compile_cache_leaves_an_outside_placement_alone(monkeypatch, tmp_path):
    from adapcc_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []  # JAX reads the variable itself; nothing is set in code


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    from adapcc_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want  # no pid, no time, no tempdir
    assert calls == [("jax_compilation_cache_dir", want)] * 2
