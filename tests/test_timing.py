"""Fetch-forced timing utilities (fedtpu.utils.timing).

Round-1 postmortem: every recorded perf number was a dispatch-rate artifact
(a timed window that ended before the device did). These utilities are the
repo-wide fix; the floor check is the guard that makes the artifact class
impossible to record again.
"""

import numpy as np
import pytest

from fedtpu.utils.timing import (Timer, assert_above_flops_floor,
                                 force_fetch)


def test_force_fetch_returns_scalar_from_tree():
    import jax.numpy as jnp
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.float32(4.0)}
    # Leaves are ordered by key: 'a' then 'b' — last leaf is b.
    assert force_fetch(tree) == 4.0


def test_force_fetch_depends_on_computation():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return {"out": (x * 2).sum(keepdims=True)}

    assert force_fetch(f(jnp.ones(5))) == 10.0


def test_force_fetch_refuses_host_only_trees():
    # A fetch that proves nothing must fail loudly, not look like success —
    # otherwise a refactor that converts metrics to numpy earlier would
    # silently reintroduce the dispatch-rate artifact.
    with pytest.raises(TypeError, match="no device-backed"):
        force_fetch({})
    with pytest.raises(TypeError, match="no device-backed"):
        force_fetch({"static": "notanarray", "np": np.ones(3)})


def test_flops_floor_passes_above_and_raises_below():
    peak = 1e12
    flops = 1e9                         # floor = 1e9 / 2e12 = 5e-4 s
    floor = assert_above_flops_floor(1e-3, flops, peak, label="ok")
    assert floor == pytest.approx(5e-4)
    with pytest.raises(RuntimeError, match="timing methodology broken"):
        # 100x faster than physics allows — the round-1 artifact shape.
        assert_above_flops_floor(5e-6, flops, peak, label="artifact")


def test_timer_laps():
    t = Timer().start()
    a = t.lap()
    b = t.lap()
    assert a >= 0 and b >= 0
    assert t.total == pytest.approx(a + b)
    assert t.mean() == pytest.approx((a + b) / 2)
