"""A run with the timed path broken underneath reads ``correct`` false,
once for each fault a cell can have (CPU, tiny sizes)."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests._tiny import run_tiny


def _unchanged_learner(monkeypatch):
    """The learner's step returns the parameters it was given."""
    import repro.runtime.service as service

    orig = service.make_slab_learner

    def broken(dqn):
        f = orig(dqn)

        def learn_slab(params, target, m, v, step0, batch, w):
            _, m2, v2, td, loss = f(params, target, m, v, step0, batch, w)
            return params, m2, v2, td, loss

        return learn_slab

    monkeypatch.setattr(service, "make_slab_learner", broken)


def _half_batch(monkeypatch):
    """Each update trains on the first half of its batch (the mean over
    those rows); the rest's TD errors come back as zeros."""
    import repro.runtime.service as service

    orig = service.make_slab_learner

    def broken(dqn):
        learn = dqn.learn

        def half(params, target, m, v, step, batch, w):
            h = w.shape[0] // 2
            p, m, v, td, loss = learn(params, target, m, v, step,
                                      jax.tree.map(lambda x: x[:h], batch),
                                      w[:h])
            return p, m, v, jnp.concatenate([td, jnp.zeros_like(td)]), loss

        return orig(dqn._replace(learn=half))

    monkeypatch.setattr(service, "make_slab_learner", broken)


def _altered_slab_draw(monkeypatch):
    """The slab draw returns one row index off by one."""
    import repro.runtime.service as service

    orig = service.make_slab_sampler

    def broken(replay, batch, slab):
        f = orig(replay, batch, slab)

        def sample_slab(state, key, beta):
            idx, tree, w, stamp = f(state, key, beta)
            return (idx.at[0, 0].set((idx[0, 0] + 1) % replay.capacity),
                    tree, w, stamp)

        return sample_slab

    monkeypatch.setattr(service, "make_slab_sampler", broken)


def _altered_draw(monkeypatch):
    """ReplayBuffer.sample returns one row index off by one."""
    from repro.core.replay_buffer import ReplayBuffer

    orig = ReplayBuffer.sample

    def broken(self, state, key, batch, beta=None):
        idx, tree, w = orig(self, state, key, batch, beta)
        return idx.at[0].set((idx[0] + 1) % self.capacity), tree, w

    monkeypatch.setattr(ReplayBuffer, "sample", broken)


def _unchanged_writeback(monkeypatch):
    """The priority write-back returns the state it was given."""
    from repro.core.replay_buffer import ReplayBuffer

    monkeypatch.setattr(ReplayBuffer, "update_priorities",
                        lambda self, state, idx, td, stamp=None: state)


@pytest.mark.parametrize("cell,fault,fails", [
    ("amper-1m.learn", _unchanged_learner, "delta"),
    ("amper-1m.learn", _half_batch, "loss"),
    ("amper-1m.learn", _altered_slab_draw, "draw"),
    ("per-1m.learn", _unchanged_learner, "delta"),
    ("per-1m.learn", _altered_slab_draw, "draw"),
    ("per-1m.learn", _altered_slab_draw, "stacks"),
    ("amper-1m.draw", _altered_draw, "draw"),
    ("amper-1m.draw", _unchanged_writeback, "priorities"),
], ids=lambda v: getattr(v, "__name__", None))
def test_fault_reads_not_correct(monkeypatch, cell, fault, fails):
    fault(monkeypatch)
    out, _ = run_tiny(cell, control=False)
    assert out["correct"] is False
    assert out["checks"][fails]["value"] > out["checks"][fails]["limit"]
