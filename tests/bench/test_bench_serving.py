"""The serving cell's comparison has to fail a broken timed path.

Each test drives a whole run of a tiny granite-shaped serving cell
through the harness on the CPU (the look for a chip skipped) with the
timed path broken underneath, and sees `correct` come out false; the
sound run of the same size comes out true."""
import jax.numpy as jnp
import pytest

import _serving


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _serving.make_root(tmp_path_factory.mktemp("servingroot"))


@pytest.fixture(scope="module")
def serving():
    return _serving.serving_module()


def _broken_step(mod, wrap):
    """A System whose engine's `step` is `wrap(step)` in the window."""

    class Broken(mod.System):
        def run(self, seconds):
            self.engine.step = wrap(self.engine.step, self)
            return super().run(seconds)

    return Broken


def test_sound_serving_run_is_correct(root, serving):
    res = _serving.run(root, serving.System)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_frozen_page_altered_is_not_correct(root, serving):
    """After each step, one symbol of the first frozen K page of every
    live slot changed, the change never corrected (the served path reads
    its memo)."""

    def wrap(step, system):
        done = set()

        def altered():
            rep = step()
            layer = next(iter(system.engine.caches.layers.values()))
            pool = system.engine.pool
            for store in layer.k_stores:
                if store is None or not store.n_pages:
                    continue
                pid = store.block_table[0]
                if pid not in done:
                    page = pool.page(pid)
                    pool.set_page(pid, page.at[0, 0].set(
                        (page[0, 0] + 1) % 3))
                    done.add(pid)
            return rep
        return altered

    res = _serving.run(root, _broken_step(serving, wrap))
    assert not res["correct"]
    assert res["checks"]["kv_pages_not_as_encoded"]["value"] >= 1


def test_kv_served_in_fp8_is_not_correct(root, serving, monkeypatch):
    """Every K/V row the engine stores (hot rows and prompt pages, so the
    frozen pages too) rounded to fp8 e4m3 at its absmax."""
    from repro.serving import engine

    def fp8(x):
        xf = x.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 448.0
        return ((xf / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * s).astype(x.dtype)

    append, ingest = engine.BatchedPagedKV.append, \
        engine.BatchedPagedKV.ingest_slot
    monkeypatch.setattr(engine.BatchedPagedKV, "append",
                        lambda self, k, v: append(self, fp8(k), fp8(v)))
    monkeypatch.setattr(engine.BatchedPagedKV, "ingest_slot",
                        lambda self, b, k, v: ingest(self, b, fp8(k), fp8(v)))
    res = _serving.run(root, serving.System)
    assert not res["correct"]
    assert res["checks"]["last_logit_diff"]["value"] > \
        _serving.TINY_LIMITS["last_logit_diff"]
    assert res["checks"]["kv_page_offset"]["value"] > \
        _serving.TINY_LIMITS["kv_page_offset"]


def _after_window(mod, spoil):
    """A System that runs `spoil(system)` once its window has closed."""

    class Spoiled(mod.System):
        def run(self, seconds):
            out = super().run(seconds)
            spoil(self)
            return out

    return Spoiled


def test_page_of_another_layer_is_not_correct(root, serving):
    """A live slot's first frozen K page of layer 0 overwritten with the
    same slot's page of layer 1: a valid codeword of int8 bytes, so only
    the comparison with the reference's page can see it."""

    def spoil(system):
        eng = system.engine
        layers = list(eng.caches.layers.values())
        b = next(i for i, s in enumerate(eng.slots) if s is not None)
        mine, other = layers[0].k_stores[b], layers[1].k_stores[b]
        eng.pool.set_page(mine.block_table[0],
                          eng.pool.page(other.block_table[0]))

    res = _serving.run(root, _after_window(serving, spoil))
    assert not res["correct"]
    assert res["checks"]["kv_pages_not_as_encoded"]["value"] == 0
    assert res["checks"]["kv_page_offset"]["value"] > \
        _serving.TINY_LIMITS["kv_page_offset"]


def test_last_logits_of_another_slot_are_not_correct(root, serving):
    """The last step's logits handed out one slot along: each request's
    row is another request's."""

    def spoil(system):
        eng = system.engine
        eng.last_logits = eng.last_logits[::-1]

    res = _serving.run(root, _after_window(serving, spoil))
    assert not res["correct"]
    assert res["checks"]["last_logit_diff"]["value"] > \
        _serving.TINY_LIMITS["last_logit_diff"]


def test_slot_tokens_dropped_from_count_is_not_correct(root, serving):
    """Each step's report leaves one slot's token out of its count."""

    def wrap(step, system):
        def dropped():
            rep = step()
            rep["tokens"] = max(rep["tokens"] - 1, 0)
            return rep
        return dropped

    res = _serving.run(root, _broken_step(serving, wrap))
    assert not res["correct"]
    assert res["checks"]["tokens_not_counted"]["value"] > 0


def test_token_altered_where_produced_is_not_correct(root, serving):
    """One slot's freshly sampled token replaced by the next id."""

    def wrap(step, system):
        def altered():
            rep = step()
            if not getattr(system, "_altered", False):
                seq = next(s for s in system.engine.slots if s is not None)
                seq.generated[-1] = (seq.generated[-1] + 1) % 512
                system._altered = True
            return rep
        return altered

    res = _serving.run(root, _broken_step(serving, wrap))
    assert not res["correct"]
    assert res["checks"]["served_logit_gap"]["value"] > \
        _serving.TINY_LIMITS["served_logit_gap"]


def test_step_that_leaves_its_state_unchanged_is_not_correct(root, serving):
    """Every other step returns a report of a full step without running
    it."""

    def wrap(step, system):
        calls = [0]

        def idle():
            calls[0] += 1
            if calls[0] % 2:
                return step()
            live = sum(s is not None for s in system.engine.slots)
            return {"step": -1, "admitted": 0, "active": live,
                    "tokens": live, "retired": 0, "preempted": 0}
        return idle

    res = _serving.run(root, _broken_step(serving, wrap))
    assert not res["correct"]
    assert res["checks"]["slot_steps_without_token"]["value"] > 0
    assert res["checks"]["tokens_not_counted"]["value"] > 0
