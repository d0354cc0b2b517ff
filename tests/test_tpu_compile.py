"""TPU v5e compiles of the done-prefix kernels at the sweeps' real shapes.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so these tests hold every done-prefix entry
point to the v5e's layout rules (block shapes tiled to (8, 128), no
VMEM scalar stores) without a chip.  ``impl="pallas"`` is passed
explicitly: ``ops._resolve("auto")`` sees this host's CPU backend.

Shapes: the forwarder grid's claim words (5 policies x 1008 lanes, 2000
packets -> 63 words), the serving grid's (5 x 2016 lanes, 1000
sessions -> 32 words), the TCP grid's (5 x 2016 lanes, 320-send budget
-> 10 words), the serving engine's default ``[1, 8]`` slot ring, a
``[4, 256]`` multi-ring mask, and one ``[8]`` ring.

One more TPU rule is checked on the traced programs, with no compile:
no fused sweep program scatters into an array narrower than 32 bits.

The topology is described only inside the module fixture below: only
one process at a time may load the TPU library, so describing it while
a module is imported would break the workers of a parallel test run.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core import SweepRequest, run_sweep  # noqa: E402
from repro.core import jaxplane as jp  # noqa: E402
from repro.kernels import ops  # noqa: E402

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host; the persistent compile
    cache is off meanwhile (entries compiled here cannot be read back
    without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize(
    "rows,n_words",
    [(5040, 63), (10080, 32), (10080, 10)],
    ids=["forwarder", "serving", "tcp"],
)
def test_packed_prefix_compiles_for_v5e(one_chip, rows, n_words):
    def fn(words, limit):
        return ops.done_prefix_packed(
            words, limit, n_bits=32 * n_words - 17, impl="pallas"
        )

    compiled = _compile(
        fn, one_chip, ((rows, n_words), jnp.uint32), ((rows,), jnp.int32)
    )
    assert KERNEL in compiled.as_text()


@pytest.mark.parametrize("rows,n", [(1, 8), (4, 256)])
def test_batch_prefix_compiles_for_v5e(one_chip, rows, n):
    def fn(done, start, limit):
        return ops.done_prefix_batch(done, start, limit, impl="pallas")

    compiled = _compile(
        fn,
        one_chip,
        ((rows, n), jnp.bool_),
        ((rows,), jnp.int32),
        ((rows,), jnp.int32),
    )
    assert KERNEL in compiled.as_text()


def test_single_prefix_compiles_for_v5e(one_chip):
    def fn(done, start, limit):
        return ops.done_prefix(done, start, limit, impl="pallas")

    compiled = _compile(
        fn, one_chip, ((8,), jnp.bool_), ((), jnp.int32), ((), jnp.int32)
    )
    assert KERNEL in compiled.as_text()


def test_multiblock_prefix_compiles_for_v5e(one_chip):
    # rows past one row block, words past one 128-word column block
    def fn(words, limit):
        return ops.done_prefix_packed(words, limit, impl="pallas", block_w=128)

    compiled = _compile(
        fn, one_chip, ((600, 300), jnp.uint32), ((600,), jnp.int32)
    )
    assert KERNEL in compiled.as_text()


class _Compiled(Exception):
    pass


def _scatter_dtypes(jaxpr) -> set:
    """Operand dtypes of every scatter in ``jaxpr`` and its sub-jaxprs."""
    found = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            found.add(np.dtype(eqn.invars[0].aval.dtype))
        todo = list(eqn.params.values())
        while todo:
            p = todo.pop()
            if isinstance(p, (tuple, list)):
                todo.extend(p)
            elif hasattr(p, "eqns"):
                found |= _scatter_dtypes(p)
            elif hasattr(p, "jaxpr") and hasattr(p.jaxpr, "eqns"):
                found |= _scatter_dtypes(p.jaxpr)
    return found


@pytest.mark.parametrize(
    "scenario,extra",
    [
        ("forwarder", {}),
        ("serving", {}),
        ("tcp", dict(tcp_params=dict(sack=False))),
        ("tcp", dict(tcp_params=dict(sack=True, loss_every=5))),
    ],
    ids=["forwarder", "serving", "tcp", "tcp-sack"],
)
def test_fused_programs_scatter_only_32bit(monkeypatch, scenario, extra):
    """No fused sweep program scatters into a narrower-than-32-bit
    array: on a TPU v5e a bool scatter in the TCP step dropped the
    ``done`` updates of whole lanes at the full grid's 2016 lanes,
    though it was exact at 256 lanes and on the CPU."""
    from repro.core import tcpjax as tj

    def trace(fn, args, static, timings):
        raise _Compiled(jax.make_jaxpr(lambda *a: fn(*a, **static))(*args))

    monkeypatch.setattr(jp, "_call_fused", trace)
    monkeypatch.setattr(tj, "_call_fused", trace)
    request = SweepRequest(
        scenario=scenario,
        policies=["corec", "hybrid"],
        seeds=np.arange(2),
        n_packets=np.array([16, 16]) if scenario == "tcp" else 32,
        **extra,
    )
    with pytest.raises(_Compiled) as got:
        run_sweep(request)
    dtypes = _scatter_dtypes(got.value.args[0].jaxpr)
    assert dtypes, "no scatter found: the walk missed the sub-jaxprs"
    assert all(d.itemsize >= 4 for d in dtypes), dtypes


def test_fused_serving_program_compiles_for_v5e(one_chip, monkeypatch):
    """The fused serving jit of two policy segments, done-prefix kernel
    included, compiles for the chip (``run_sweep`` builds the program;
    its arguments are handed over as shapes on the described device)."""
    real = jp._fused_jit

    class AotOnChip:
        def __init__(self, fn):
            self.fn = fn

        def lower(self, *args, **static):
            shapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                args,
            )
            static = dict(static, prefix_impl="pallas")
            raise _Compiled(self.fn.lower(*shapes, **static).compile())

    monkeypatch.setattr(jp, "_fused_jit", lambda donate: AotOnChip(real(True)))
    request = SweepRequest(
        scenario="serving",
        policies=["corec", "scaleout"],
        seeds=np.arange(8),
        arrival="diurnal",
        traffic_params=dict(rate=4.0),
        serving_params=dict(admit_limit=24.0, base_workers=2.0, slo_target=30.0),
        n_packets=128,
        max_batch=32,
    )
    with pytest.raises(_Compiled) as got:
        run_sweep(request, timings={})
    assert got.value.args[0].as_text().count(KERNEL) == 1
