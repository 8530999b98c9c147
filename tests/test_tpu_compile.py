"""Mosaic compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is only described (``v5e:2x2``), and refuses what the
chip's compiler would refuse — a kernel Mosaic cannot lower, more scoped
VMEM than a kernel may use.  Each case compiles one kernel, called the way
a user calls it (``registry.dispatch`` with ``ssr=True`` and
``interpret=False``), at the widths ``chip_smoke.py`` runs on the chip
(qwen3-14b layers, f32), and asserts that the compiled HLO holds the
Mosaic kernel (``tpu_custom_call``).  Nothing runs, so these say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The last test runs ``chip_smoke.py``'s phase functions
at tiny sizes in interpret mode on the CPU.
"""

import importlib.util
import os
import re
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import autotune, compiler, lowering, ssr
from repro.core.lowering import Schedule
from repro.kernels import frontend, registry


@pytest.fixture(scope="module")
def sched_cache(tmp_path_factory):
    """An empty schedule cache: every compile takes the default schedule."""
    old = os.environ.get("REPRO_SCHEDULE_CACHE")
    os.environ["REPRO_SCHEDULE_CACHE"] = str(tmp_path_factory.mktemp("sched"))
    yield
    if old is None:
        del os.environ["REPRO_SCHEDULE_CACHE"]
    else:
        os.environ["REPRO_SCHEDULE_CACHE"] = old


@pytest.fixture(scope="module")
def topo(sched_cache):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _lowered(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args)


def _compiled_text(fn, shapes, sharding) -> str:
    return _lowered(fn, shapes, sharding).compile().as_text()


def _ssr(name, **kw):
    return lambda *a: registry.dispatch(name, *a, ssr=True, interpret=False,
                                        **kw)


def _heads(fn, heads=40, kv_heads=8):
    """vmap a single-head kernel over GQA query heads (K/V expanded)."""
    group = heads // kv_heads

    def run(q, k, v):
        return jax.vmap(fn)(q, jnp.repeat(k, group, 0),
                            jnp.repeat(v, group, 0))
    return run


#: (case, callable, operand shapes) — the chip_smoke.py main-path sizes.
MAIN_PATH = [
    ("gemm", _ssr("gemm"), [(2048, 5120), (5120, 17408)]),
    ("attention", _heads(_ssr("attention", causal=True)),
     [(40, 2048, 128), (8, 2048, 128), (8, 2048, 128)]),
    # h2o-danube-1.8b: window 4096 over 16384 tokens, head dim 80 unpadded
    ("attention_window", _heads(_ssr("attention", causal=True, window=4096),
                                heads=32),
     [(32, 16384, 80), (8, 16384, 80), (8, 16384, 80)]),
    ("gemv", _ssr("gemv"), [(5120, 17408), (17408,)]),
    ("gemv_depth3", _ssr("gemv", schedule=Schedule(buffer_depth=3)),
     [(5120, 17408), (17408,)]),
    ("reduction", _ssr("reduction"), [(64 << 20,), (64 << 20,)]),
    ("stencil1d", _ssr("stencil1d"), [((16 << 20) + 10,), (11,)]),
    ("mlp_block", _ssr("mlp_block"),
     [(65536, 128), (128, 128), (128,), (128, 128), (128,)]),
]


#: The kernel family of each main-path case compiled through NestKernel.
FAMILY = {"gemm": "gemm", "attention": "attention",
          "attention_window": "attention", "gemv": "gemv",
          "gemv_depth3": "gemv", "reduction": "reduction",
          "stencil1d": "stencil1d"}


@pytest.mark.parametrize("case,fn,shapes", MAIN_PATH,
                         ids=[c[0] for c in MAIN_PATH])
def test_main_path_compiles_for_v5e(one_chip, case, fn, shapes):
    before = lowering.DISPATCH_STATS["pipelined"]
    lowered = _lowered(fn, shapes, one_chip)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    if case == "gemv_depth3":
        # the N-deep DMA emitter, not a silent fall back to the sync path
        assert lowering.DISPATCH_STATS["pipelined"] > before
    if case in FAMILY:
        # the jitted pipeline and its Mosaic kernel carry the family's
        # name, so a device trace shows jit_ssr_<family> and %ssr_<family>
        name = f"ssr_{FAMILY[case]}"
        assert f"func.func private @{name}(" in lowered.as_text()
        kernels = [ln.split("=", 1)[0].strip() for ln in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln]
        assert kernels
        assert all(k.removeprefix("ROOT ").startswith(f"%{name}.")
                   for k in kernels), kernels


@pytest.mark.parametrize("name", ["scan", "bitonic"])
def test_repaired_kernel_compiles_for_v5e(one_chip, name):
    # Mosaic refused these before: scan's cumsum, bitonic's 3-D shape casts
    args, kw = registry.get(name).example(np.random.default_rng(0))
    text = _compiled_text(_ssr(name, **kw), [a.shape for a in args],
                          one_chip)
    assert "tpu_custom_call" in text


def _scoped_vmem(lowered_text: str):
    """The scoped-VMEM limit a lowered Mosaic kernel declares, or None."""
    m = re.search(r"scoped_memory_configs.*?size\\22: (\d+)", lowered_text)
    return None if m is None else int(m.group(1))


#: GEMMs of the benchmark's cells, (A shape, B shape): qwen3-14b gate/up,
#: yi-6b gate/up and down at 2048 tokens, one four-chip shard of qwen3-14b's
#: gate/up at 8192 tokens.
TRAFFIC_GEMMS = [
    ("qwen3_gate_up", (2048, 5120), (5120, 17408)),
    ("yi_gate_up", (2048, 4096), (4096, 11008)),
    ("yi_down", (2048, 11008), (11008, 4096)),
    ("qwen3_x4_gate_up_shard", (4096, 5120), (5120, 8704)),
]


@pytest.mark.parametrize("case,a,b", TRAFFIC_GEMMS,
                         ids=[c[0] for c in TRAFFIC_GEMMS])
def test_traffic_tiles_compile_for_v5e(one_chip, cold_kernels, case, a, b):
    # the tiles the HBM-traffic model picks, with the VMEM limit they need
    nest = compiler.gemm_nest(a[0], b[1], a[1])
    sched = autotune.traffic_schedule(
        nest, {"A": (a, "float32"), "B": (b, "float32")})
    assert sched is not None
    ws = autotune._stream_block_bytes(autotune._lower_candidate(nest, sched))
    before = frontend.DISPATCH_STATS["traffic_tiles"]
    lowered = _lowered(_ssr("gemm"), [a, b], one_chip)
    assert frontend.DISPATCH_STATS["traffic_tiles"] == before + 1
    assert _scoped_vmem(lowered.as_text()) == ssr.vmem_limit_bytes(ws)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("case", ["gemv", "attention"])
def test_default_kernels_declare_no_vmem_limit(one_chip, case):
    # kernels that fit Mosaic's default scoped VMEM compile as they did
    _case, fn, shapes = next(c for c in MAIN_PATH if c[0] == case)
    assert _scoped_vmem(_lowered(fn, shapes, one_chip).as_text()) is None


#: Yi-6B attention as the benchmark's stage calls it: decode, one vmap over
#: 4 KV heads (8 stacked queries over a 4096-position cache); prefill, the
#: group's 8 q heads inside and the KV heads outside.
YI_VMAPPED = {
    "decode": (lambda f: jax.vmap(f),
               [(4, 8, 128), (4, 4096, 128), (4, 4096, 128)], {}),
    "prefill": (lambda f: jax.vmap(jax.vmap(f, in_axes=(0, None, None))),
                [(4, 8, 2048, 128), (4, 2048, 128), (4, 2048, 128)],
                {"causal": True}),
}


@pytest.mark.parametrize("case", sorted(YI_VMAPPED))
def test_peeled_vmap_is_the_same_mosaic_program(one_chip, monkeypatch, case):
    # a call under vmap, served by a cached jit(vmap(pipeline)), lowers to
    # the Mosaic program that JAX's batching of the pipeline gives
    wrap, shapes, kw = YI_VMAPPED[case]
    peeled = _lowered(wrap(_ssr("attention", **kw)), shapes,
                      one_chip).as_text()
    monkeypatch.setattr(frontend._Peeled, "of",
                        classmethod(lambda cls, args, params: None))
    unpeeled = _lowered(wrap(_ssr("attention", **kw)), shapes,
                        one_chip).as_text()
    assert "tpu_custom_call" in peeled
    assert peeled == unpeeled


# --------------------------------------------------------------------------
# chip_smoke.py's phases at tiny sizes, interpreted on the CPU
# --------------------------------------------------------------------------


def _chip_smoke():
    if "chip_smoke" not in sys.modules:
        path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
        spec = importlib.util.spec_from_file_location("chip_smoke", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = mod    # dataclasses resolve it by name
        spec.loader.exec_module(mod)
    return sys.modules["chip_smoke"]


TINY_PHASES = {
    "gemm": lambda cs: cs.phase_gemm(64, 256, 384, interpret=True),
    "gemv": lambda cs: cs.phase_gemv(40, 384, interpret=True),
    "gemv_depth3": lambda cs: cs.phase_gemv(64, 2048, depth=3,
                                            interpret=True),
    "attention": lambda cs: cs.phase_attention(4, 2, 128, 64,
                                               interpret=True),
    "reduction": lambda cs: cs.phase_dot(5000, interpret=True),
    "stencil1d": lambda cs: cs.phase_stencil1d(3000, interpret=True),
    "mlp_block": lambda cs: cs.phase_mlp_block(64, interpret=True),
    "scan": lambda cs: cs.phase_example("scan", interpret=True),
}


@pytest.fixture
def cold_kernels():
    """Every kernel's pipeline cache empty, as in ``chip_smoke.py``'s own
    fresh process: a phase must build what it runs, whatever ran earlier
    in this test process."""
    from repro.kernels import frontend

    registry.names()                     # imports every kernel module
    for mod in list(sys.modules.values()):
        if getattr(mod, "__name__", "").startswith("repro.kernels."):
            for v in vars(mod).values():
                if isinstance(v, (frontend._KernelBase, frontend.NestKernel)):
                    v._cache.clear()
    lowering.clear_caches()


@pytest.mark.parametrize("phase", sorted(TINY_PHASES))
def test_chip_smoke_phase_tiny_interpret(sched_cache, cold_kernels, phase):
    res = TINY_PHASES[phase](_chip_smoke())
    assert res.passed, res.line()
    assert res.builds > 0 and not res.custom_call
    if phase == "gemv_depth3":
        assert "pipelined=True" in res.note


# --------------------------------------------------------------------------
# The persistent compile cache the entry points place
# --------------------------------------------------------------------------


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, placed):
    from repro.launch import compile_cache

    root = Path(__file__).resolve().parents[1]
    was = jax.config.jax_compilation_cache_dir
    try:
        if placed:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was  # set nothing
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = compile_cache.enable_compile_cache()
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            ignored = (root / ".gitignore").read_text().split()
            assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
