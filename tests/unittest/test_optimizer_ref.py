"""Optimizer-vs-python-reference checks.

Reference: tests/python/unittest/test_optimizer.py — every optimizer is
stepped alongside an independent numpy implementation of its published
update rule (mxnet 0.11 semantics) and the trajectories must match.
Also covers the fused update ops directly and the LR schedulers.
"""
import math

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import optimizer as opt
from mxnet_tpu import lr_scheduler
from mxnet_tpu.test_utils import assert_almost_equal

STEPS = 5
SHAPE = (3, 4)


def _run(optimizer, seed=0, steps=STEPS, shape=SHAPE, dtype=np.float32):
    """Step `optimizer` on random grads; return (weight trajectory, grads)."""
    rng = np.random.RandomState(seed)
    w0 = rng.randn(*shape).astype(dtype)
    grads = [rng.randn(*shape).astype(dtype) for _ in range(steps)]
    weight = nd.array(w0)
    state = optimizer.create_state(0, weight)
    traj = []
    for g in grads:
        optimizer.update(0, weight, nd.array(g), state)
        traj.append(weight.asnumpy().copy())
    return w0, grads, traj


def _clip(g, c):
    return np.clip(g, -c, c) if c is not None else g


class TestSGD:
    @pytest.mark.parametrize('momentum,wd,clip,rescale', [
        (0.0, 0.0, None, 1.0),
        (0.9, 0.0, None, 1.0),
        (0.9, 0.01, None, 1.0),
        (0.0, 0.05, 0.5, 1.0),
        (0.9, 0.01, 0.5, 0.25),
    ])
    def test_vs_numpy(self, momentum, wd, clip, rescale):
        o = opt.SGD(learning_rate=0.1, momentum=momentum, wd=wd,
                    clip_gradient=clip, rescale_grad=rescale)
        w0, grads, traj = _run(o)
        w = w0.copy()
        mom = np.zeros_like(w)
        for g, got in zip(grads, traj):
            g = _clip(g * rescale, clip)
            mom = momentum * mom - 0.1 * (g + wd * w)
            w = w + mom
            assert_almost_equal(got, w, rtol=1e-5, atol=1e-6)

    def test_lr_mult_wd_mult(self):
        o = opt.SGD(learning_rate=0.1, wd=0.1,
                    param_idx2name={0: 'fc_weight'})
        o.set_lr_mult({'fc_weight': 0.5})
        o.set_wd_mult({'fc_weight': 2.0})
        w0, grads, traj = _run(o, steps=1)
        w = w0 - 0.05 * (grads[0] + 0.2 * w0)
        assert_almost_equal(traj[0], w, rtol=1e-5)

    def test_non_weight_params_get_no_wd(self):
        # reference behavior: names not ending _weight/_gamma get wd_mult=0
        o = opt.SGD(learning_rate=0.1, wd=0.5,
                    param_idx2name={0: 'fc_bias'})
        w0, grads, traj = _run(o, steps=1)
        assert_almost_equal(traj[0], w0 - 0.1 * grads[0], rtol=1e-5)


class TestNAG:
    def test_vs_numpy(self):
        o = opt.NAG(learning_rate=0.1, momentum=0.9, wd=0.01)
        w0, grads, traj = _run(o)
        w = w0.copy()
        mom = np.zeros_like(w)
        for g, got in zip(grads, traj):
            g = g + 0.01 * w
            mom = 0.9 * mom + g
            g = g + 0.9 * mom
            w = w - 0.1 * g
            assert_almost_equal(got, w, rtol=1e-5, atol=1e-6)


class TestAdam:
    @pytest.mark.parametrize('wd,clip', [(0.0, None), (0.01, None),
                                         (0.01, 0.5)])
    def test_vs_numpy(self, wd, clip):
        o = opt.Adam(learning_rate=0.01, beta1=0.9, beta2=0.999,
                     epsilon=1e-8, wd=wd, clip_gradient=clip)
        w0, grads, traj = _run(o)
        w = w0.copy()
        m = np.zeros_like(w)
        v = np.zeros_like(w)
        for t, (g, got) in enumerate(zip(grads, traj), 1):
            lr = 0.01 * math.sqrt(1 - 0.999 ** t) / (1 - 0.9 ** t)
            g = _clip(g, clip) + wd * w
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * g * g
            w = w - lr * m / (np.sqrt(v) + 1e-8)
            assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)


class TestAdaGrad:
    def test_vs_numpy(self):
        o = opt.AdaGrad(learning_rate=0.1, eps=1e-7, wd=0.01)
        w0, grads, traj = _run(o)
        w = w0.copy()
        h = np.zeros_like(w)
        for g, got in zip(grads, traj):
            h = h + g * g
            w = w - 0.1 * (g / np.sqrt(h + 1e-7) + 0.01 * w)
            assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)


class TestRMSProp:
    def test_plain_vs_numpy(self):
        o = opt.RMSProp(learning_rate=0.01, gamma1=0.9, epsilon=1e-8)
        w0, grads, traj = _run(o)
        w = w0.copy()
        n = np.zeros_like(w)
        for g, got in zip(grads, traj):
            n = 0.1 * g * g + 0.9 * n
            w = w - 0.01 * g / np.sqrt(n + 1e-8)
            assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)

    def test_centered_vs_numpy(self):
        o = opt.RMSProp(learning_rate=0.01, gamma1=0.9, gamma2=0.8,
                        epsilon=1e-8, centered=True)
        w0, grads, traj = _run(o)
        w = w0.copy()
        n = np.zeros_like(w)
        gs = np.zeros_like(w)
        d = np.zeros_like(w)
        for g, got in zip(grads, traj):
            n = 0.1 * g * g + 0.9 * n
            gs = 0.1 * g + 0.9 * gs
            d = 0.8 * d - 0.01 * g / np.sqrt(n - gs * gs + 1e-8)
            w = w + d
            assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)

    def test_clip_weights(self):
        o = opt.RMSProp(learning_rate=5.0, gamma1=0.9, clip_weights=0.2)
        _, _, traj = _run(o)
        assert np.abs(traj[-1]).max() <= 0.2 + 1e-7


class TestAdaDelta:
    def test_vs_numpy(self):
        o = opt.AdaDelta(rho=0.9, epsilon=1e-5, wd=0.01)
        w0, grads, traj = _run(o)
        w = w0.copy()
        acc_g = np.zeros_like(w)
        acc_d = np.zeros_like(w)
        for g, got in zip(grads, traj):
            acc_g = 0.9 * acc_g + 0.1 * g * g
            delta = np.sqrt(acc_d + 1e-5) / np.sqrt(acc_g + 1e-5) * g
            acc_d = 0.9 * acc_d + 0.1 * delta * delta
            w = w - delta - 0.01 * w
            assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)


class TestFtrl:
    def test_vs_numpy(self):
        o = opt.Ftrl(learning_rate=0.1, lamda1=0.01, beta=1.0, wd=0.01)
        w0, grads, traj = _run(o)
        w = w0.copy()
        z = np.zeros_like(w)
        n = np.zeros_like(w)
        for g, got in zip(grads, traj):
            z = z + g - (np.sqrt(n + g * g) - np.sqrt(n)) / 0.1 * w
            n = n + g * g
            w = (np.sign(z) * 0.01 - z) / ((1.0 + np.sqrt(n)) / 0.1 + 0.01) \
                * (np.abs(z) > 0.01)
            assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)

    def test_l1_produces_sparsity(self):
        # from a zero start, |z| stays below a huge l1 → weights pinned at 0
        o = opt.Ftrl(learning_rate=0.1, lamda1=100.0)
        rng = np.random.RandomState(0)
        weight = nd.zeros(SHAPE)
        state = o.create_state(0, weight)
        for _ in range(5):
            o.update(0, weight, nd.array(rng.randn(*SHAPE).astype(np.float32)),
                     state)
        assert (weight.asnumpy() == 0).all()


class TestAdamax:
    def test_vs_numpy(self):
        o = opt.Adamax(learning_rate=0.002, beta1=0.9, beta2=0.999, wd=0.01)
        w0, grads, traj = _run(o)
        w = w0.copy()
        m = np.zeros_like(w)
        u = np.zeros_like(w)
        for t, (g, got) in enumerate(zip(grads, traj), 1):
            lr = 0.002 / (1 - 0.9 ** t)
            g = g + 0.01 * w
            m = 0.9 * m + 0.1 * g
            u = np.maximum(0.999 * u, np.abs(g))
            w = w - lr * m / u
            assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)


class TestNadam:
    def test_vs_numpy(self):
        o = opt.Nadam(learning_rate=0.001, beta1=0.9, beta2=0.999,
                      epsilon=1e-8, schedule_decay=0.004)
        w0, grads, traj = _run(o)
        w = w0.copy()
        m = np.zeros_like(w)
        v = np.zeros_like(w)
        m_schedule = 1.0
        for t, (g, got) in enumerate(zip(grads, traj), 1):
            mom_t = 0.9 * (1 - 0.5 * 0.96 ** (t * 0.004))
            mom_t1 = 0.9 * (1 - 0.5 * 0.96 ** ((t + 1) * 0.004))
            m_schedule = m_schedule * mom_t
            m_schedule_next = m_schedule * mom_t1
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * g * g
            g_prime = g / (1 - m_schedule)
            m_prime = m / (1 - m_schedule_next)
            v_prime = v / (1 - 0.999 ** t)
            m_bar = (1 - mom_t) * g_prime + mom_t1 * m_prime
            w = w - 0.001 * m_bar / (np.sqrt(v_prime) + 1e-8)
            assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)


class TestDCASGD:
    def test_vs_numpy(self):
        o = opt.DCASGD(learning_rate=0.1, momentum=0.0, lamda=0.04)
        w0, grads, traj = _run(o)
        w = w0.copy()
        prev = w0.copy()
        for g, got in zip(grads, traj):
            mon = -0.1 * (g + 0.04 * g * g * (w - prev))
            prev = w.copy()
            w = w + mon
            assert_almost_equal(got, w, rtol=1e-4, atol=1e-6)


class TestTestOptimizer:
    def test_exact_accumulation(self):
        o = opt.Test(rescale_grad=0.5)
        w0, grads, traj = _run(o, steps=3)
        w = w0.copy()
        for g, got in zip(grads, traj):
            w = w + 0.5 * g
            assert_almost_equal(got, w, rtol=1e-6)


class TestSGLD:
    def test_mean_drift_matches(self):
        # stochastic: check expected drift over many steps on zero grads
        mx.random.seed(0)
        o = opt.SGLD(learning_rate=0.0001, wd=0.0)
        weight = nd.zeros((10000,))
        for _ in range(2):
            o.update(0, weight, nd.zeros((10000,)), None)
        x = weight.asnumpy()
        # noise std per step = sqrt(lr) = 0.01; two steps → sqrt(2)*0.01
        assert abs(x.std() - math.sqrt(2) * 0.01) < 0.002
        assert abs(x.mean()) < 0.001


class TestCreateAndUpdater:
    def test_create_by_name(self):
        for name in ['sgd', 'adam', 'rmsprop', 'adagrad', 'adadelta',
                     'ftrl', 'adamax', 'nadam', 'nag', 'test', 'dcasgd',
                     'sgld', 'ccsgd']:
            o = opt.create(name)
            assert isinstance(o, opt.Optimizer), name

    def test_updater_state_roundtrip(self):
        o = opt.SGD(learning_rate=0.1, momentum=0.9)
        u = opt.get_updater(o)
        w = nd.array(np.ones(SHAPE, np.float32))
        u(0, nd.array(np.ones(SHAPE, np.float32)), w)
        states = u.get_states()
        o2 = opt.SGD(learning_rate=0.1, momentum=0.9)
        u2 = opt.get_updater(o2)
        u2.set_states(states)
        w2 = w.copy()
        u(0, nd.array(np.ones(SHAPE, np.float32)), w)
        u2(0, nd.array(np.ones(SHAPE, np.float32)), w2)
        assert_almost_equal(w.asnumpy(), w2.asnumpy(), rtol=1e-6)


class TestFusedOps:
    def test_sgd_update_op(self):
        w = np.array([1.0, 2.0], np.float32)
        g = np.array([0.5, -0.5], np.float32)
        out = nd.sgd_update(nd.array(w), nd.array(g), lr=0.1, wd=0.1)
        assert_almost_equal(out.asnumpy(), w - 0.1 * (g + 0.1 * w),
                            rtol=1e-6)

    def test_sgd_update_mutates_in_place(self):
        w = nd.array(np.array([1.0, 2.0], np.float32))
        nd.sgd_update(w, nd.array(np.array([1.0, 1.0], np.float32)),
                      out=w, lr=0.1)
        assert_almost_equal(w.asnumpy(), np.array([0.9, 1.9], np.float32),
                            rtol=1e-6)

    def test_mp_sgd_keeps_fp32_master(self):
        w16 = nd.array(np.array([1.0, 2.0], np.float32)).astype('float16')
        w32 = nd.array(np.array([1.0, 2.0], np.float32))
        g16 = nd.array(np.array([1e-4, 1e-4], np.float32)).astype('float16')
        for _ in range(10):
            nd.mp_sgd_update(w16, g16, w32, out=w16, lr=1.0)
        # master accumulates updates below fp16 resolution at 2.0
        assert w32.asnumpy()[1] < 2.0 - 5e-4

    def test_adam_update_op_states(self):
        w = nd.array(np.ones(2, np.float32))
        g = nd.array(np.full(2, 0.5, np.float32))
        mean = nd.zeros((2,))
        var = nd.zeros((2,))
        nd.adam_update(w, g, mean, var, out=w, lr=0.1, beta1=0.9,
                       beta2=0.99, epsilon=1e-8)
        assert_almost_equal(mean.asnumpy(), np.full(2, 0.05, np.float32),
                            rtol=1e-5)
        assert_almost_equal(var.asnumpy(), np.full(2, 0.0025, np.float32),
                            rtol=1e-5)


class TestLRScheduler:
    def test_factor_scheduler(self):
        # reference semantics: lr drops once num_update EXCEEDS the step
        s = lr_scheduler.FactorScheduler(step=10, factor=0.5)
        s.base_lr = 1.0
        assert s(5) == 1.0
        assert s(10) == 1.0
        assert s(11) == pytest.approx(0.5)
        assert s(21) == pytest.approx(0.25)

    def test_multifactor_scheduler(self):
        s = lr_scheduler.MultiFactorScheduler(step=[5, 15], factor=0.1)
        s.base_lr = 1.0
        assert s(1) == 1.0
        assert s(6) == pytest.approx(0.1)
        assert s(16) == pytest.approx(0.01)

    def test_scheduler_drives_optimizer(self):
        sched = lr_scheduler.FactorScheduler(step=2, factor=0.5)
        o = opt.SGD(learning_rate=1.0, lr_scheduler=sched)
        w = nd.zeros((1,))
        g = nd.array(np.array([1.0], np.float32))
        o.update(0, w, g, None)        # num_update=1, lr=1.0 → w=-1
        o.update(0, w, g, None)        # num_update=2, lr=1.0 → w=-2
        o.update(0, w, g, None)        # num_update=3 > step → lr=0.5
        assert_almost_equal(w.asnumpy(), np.array([-2.5], np.float32),
                            rtol=1e-5)
