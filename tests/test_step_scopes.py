"""The step bodies' named scopes and the op -> scope map read from a
built step's compiled text: the scopes reach the map, and they change
only the program's metadata."""
import contextlib
import re

import jax
import pytest

from repro.configs import registry
from repro.configs.base import OptimizerConfig, ShapeConfig
from repro.launch.mesh import single_device_mesh
from repro.runtime import steps as steps_mod


@pytest.fixture(scope="module")
def setup():
    return dict(cfg=registry.get_smoke("phi4-mini-3.8b"),
                par=registry.get_parallel("phi4-mini-3.8b"),
                mesh=single_device_mesh())


def _decode(s):
    return steps_mod.build_paged_decode(
        s["cfg"], s["par"], s["mesh"], ShapeConfig("serve", 16, 2, "decode"),
        block_size=4, num_blocks=12)


def _train(s):
    return steps_mod.build_train_chunk(
        s["cfg"], s["par"], OptimizerConfig(warmup_steps=1, decay_steps=10),
        s["mesh"], ShapeConfig("t", 32, 2, "train"), 1)


def _scopes_of(scopes):
    return {part for path in scopes.values() for part in path.split("/")}


@pytest.mark.parametrize("build, module, want", [
    (_decode, "jit_paged_step",
     {"kv_gather", "kv_write", "kv_scatter", "attn", "mlp", "lm_head"}),
    (_train, "jit_train_chunk", {"attn", "mlp", "lm_head"}),
])
def test_op_scopes_name_the_compiled_ops(setup, build, module, want):
    bundle = build(setup)
    assert bundle.module_name == module
    bundle.jit()
    scopes = steps_mod.op_scopes(module)
    assert want <= _scopes_of(scopes)
    text = bundle.lower().compile().as_text()
    names = re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", text, re.M)
    assert set(names) == set(scopes)         # every op of the program
    assert steps_mod.op_scopes(module) is scopes    # kept


@pytest.mark.parametrize("arch, scope", [("phi4-mini-3.8b", "attn"),
                                         ("kimi-k2", "mla")])
def test_paged_kernel_sits_in_the_attention_scope(setup, monkeypatch, arch,
                                                  scope):
    """With the paged attention kernel in the step (as on a TPU; here
    forced on and interpreted), every op of the kernel maps to the
    layer's attention scope (``attn``, MLA's ``mla``), the view path's
    ``kv_gather`` and ``kv_write`` are gone, the write-back stays
    ``kv_scatter``'s, and the map names every op of the program.  (The
    Mosaic custom call's own scope: tests/test_tpu_compile.py.)"""
    from bench.model_mla_moe import program_config
    from repro.kernels import paged_decode
    from test_mla_moe import tiny_config
    monkeypatch.setattr(paged_decode, "kernel_path", lambda *a: True)
    cfg = (setup["cfg"] if arch != "kimi-k2"
           else program_config(tiny_config("bfloat16")))
    bundle = steps_mod.build_paged_decode(
        cfg, registry.get_parallel(cfg.name), setup["mesh"],
        ShapeConfig("serve", 16, 2, "decode"), block_size=4, num_blocks=12)
    bundle.jit()
    scopes = steps_mod.op_scopes("jit_paged_step")
    kernel = [path.split("/") for path in scopes.values()
              if "paged_decode" in path.split("/")]
    assert kernel and all(
        scope in parts[:parts.index("paged_decode")] for parts in kernel)
    names = _scopes_of(scopes)
    assert "kv_scatter" in names and not {"kv_gather", "kv_write"} & names
    text = bundle.lower().compile().as_text()
    assert set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", text,
                          re.M)) == set(scopes)


def test_train_backward_inherits_the_head_scope(setup):
    _train(setup).jit()
    scopes = steps_mod.op_scopes("jit_train_chunk")
    text = steps_mod._BUILT["jit_train_chunk"].lower().compile().as_text()
    backward = re.findall(
        r'%?([\w.\-]+) = [^\n]*op_name="[^"]*transpose\([^"]*/lm_head/',
        text)
    assert backward and all("lm_head" in scopes[n].split("/")
                            for n in backward)


def test_unknown_module_is_a_key_error():
    with pytest.raises(KeyError):
        steps_mod.op_scopes("jit_never_built")


def test_scope_path_unwraps_transforms():
    path = steps_mod._scope_path
    assert path("jit(f)/transpose(jvp(lm_head))/dot_general") == \
        "f/lm_head"
    assert path("jit(paged_step)/while/body/attn/kv_write/select_n") == \
        "paged_step/while/body/attn/kv_write"
    assert path("jit(f)/jvp()/mul") == "f"
    assert path("param") == ""


@pytest.mark.parametrize("build", [_decode, _train])
def test_scopes_change_only_metadata(setup, build, monkeypatch):
    def program(bundle):
        with setup["mesh"]:
            text = bundle.lower().compile().as_text()
        text = re.sub(r",? metadata=\{[^}]*\}", "", text)
        return text[:text.find("\nFileNames")] if "\nFileNames" in text \
            else text

    scoped = program(build(setup))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert program(build(setup)) == scoped


def test_op_scopes_of_a_step_whose_jit_a_caller_wrapped(setup):
    """A caller may replace a bundle's ``jit`` with one returning a plain
    function (the benchmark's training watcher does); the map is made
    from the bundle itself, and the cache-key setting is restored."""
    bundle = _train(setup)
    jitted = bundle.jit

    def wrapped():
        fn = jitted()
        return lambda *a: fn(*a)

    bundle.jit = wrapped
    bundle.jit()
    assert "jit_train_chunk" in steps_mod._BUILT
    scopes = steps_mod.op_scopes("jit_train_chunk")
    assert "lm_head" in _scopes_of(scopes)
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False
