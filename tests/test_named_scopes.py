"""The train step's named scopes reach the compiled program's ``op_name``
metadata, where the device trace's reduction (bench/phases.py) finds them:
a refactor that drops one fails here."""

import re
from dataclasses import replace

import jax
import pytest

from repro.configs import get_config
from repro.configs.base import ParallelConfig
from repro.core.shadow import _abstract_opt, abstract_batch
from repro.distribution.sharding import make_elastic_mesh
from repro.distribution.step import FWD_SCOPE, OPT_SCOPE, jit_train_step, jit_update_step
from repro.models.model import abstract_params
from repro.optim import AdamWConfig

OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def setting():
    cfg = replace(get_config("qwen3-1.7b").reduced(), num_layers=2)
    mesh = make_elastic_mesh(ParallelConfig(), devices=jax.devices()[:1])
    aparams = abstract_params(cfg)
    return cfg, mesh, aparams, _abstract_opt(cfg, aparams, "none")


def _op_names(lowered) -> list[str]:
    # the compiled text: after inlining, every op carries its whole path
    return OP_NAME.findall(lowered.compile().as_text())


def test_the_train_step_names_its_phases(setting, monkeypatch):
    # the flash kernel's own backward rule runs only on the kernel path
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    cfg, mesh, aparams, aopt = setting
    jitted, _ = jit_train_step(cfg, mesh, AdamWConfig(), 2)
    names = _op_names(jitted.lower(aparams, aopt, abatch := abstract_batch(cfg, 2, 128)))
    assert abatch["tokens"].shape == (2, 128)

    def having(*parts):
        return [n for n in names if all(p in n for p in parts)]

    fwd = [n for n in having(FWD_SCOPE) if "transpose(" not in n]
    bwd = having(f"transpose(jvp({FWD_SCOPE}))")
    assert fwd and bwd and having(OPT_SCOPE)
    for scope in ("attn", "mlp", "head", "embed"):
        assert any(f"/{scope}/" in n for n in fwd), scope
        assert any(f"/{scope}/" in n for n in bwd), scope
    # the attention backward is its own scope inside the backward pass
    assert having(f"transpose(jvp({FWD_SCOPE}))", "/attn/", "flash_bwd/")
    # the forward recomputed under remat is told apart by its name
    assert having(f"transpose(jvp({FWD_SCOPE}))", "rematted_computation")
    # the optimizer's ops sit under no forward scope
    assert not having(OPT_SCOPE, FWD_SCOPE)


def test_the_update_step_names_its_phase(setting):
    cfg, mesh, aparams, aopt = setting
    jitted, _ = jit_update_step(cfg, mesh, AdamWConfig())
    names = _op_names(jitted.lower(aparams, aopt, aparams))
    scoped = [n for n in names if "/" in n]
    assert scoped and all(OPT_SCOPE in n for n in scoped)
