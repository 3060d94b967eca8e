"""The reference's model of the DMS arena is the program's: for random
eviction decisions, the step at which each token leaves the arena
(``evict_times``) matches the slots ``SlotDMSCache`` keeps valid, through
delayed eviction and through overflow recycling."""
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.references import dense_gqa as ref
from repro.core.kv_cache import SlotDMSCache


@pytest.mark.parametrize("p_evict,slots", [(0.9, 24), (0.5, 24), (0.0, 24),
                                           (0.7, 40)])
def test_evict_times_match_the_slot_arena(p_evict, slots):
    window, steps, heads = 6, 120, 3
    rng = np.random.default_rng(int(p_evict * 10) + slots)
    alpha = rng.random((steps, heads)) < p_evict
    cache = SlotDMSCache.init(1, heads, slots, 4, window, jnp.float32)
    e = np.stack([ref.evict_times(alpha[:, h], window, slots)
                  for h in range(heads)], -1)                    # (T, H)
    for t in range(steps):
        k = jnp.full((1, heads, 1, 4), float(t))
        cache = cache.step(k, k, jnp.asarray(alpha[t][None]))
        live = np.zeros((steps, heads), bool)
        pos, valid = np.asarray(cache.pos[0]), np.asarray(cache.valid[0])
        for h in range(heads):
            live[pos[h][valid[h]], h] = True
        want = (np.arange(steps)[:, None] <= t) & (t < e)
        assert (live == want).all(), f"step {t}"


def test_no_eviction_never_evicts():
    e = ref.evict_times(np.zeros(50, bool), 4, 100)
    assert (e == ref.NEVER).all()
