"""Optimizer op tests: one update step vs numpy formulas (reference
test_sgd_op.py, test_momentum_op.py, test_adam_op.py ...)."""
import numpy as np
import pytest

from op_test import OpTest


def _rand(shape, seed=0, lo=-1.0, hi=1.0):
    return np.random.RandomState(seed).uniform(lo, hi,
                                               shape).astype('float32')


def test_sgd():
    class T(OpTest):
        op_type = 'sgd'

        def setup(self):
            p = _rand((4, 3), 1)
            g = _rand((4, 3), 2)
            lr = np.array([0.1], 'float32')
            self.inputs = {'Param': p, 'Grad': g, 'LearningRate': lr}
            self.attrs = {}
            self.outputs = {'ParamOut': p - 0.1 * g}
    T().check_output()


@pytest.mark.parametrize('nesterov', [False, True])
def test_momentum(nesterov):
    class T(OpTest):
        op_type = 'momentum'

        def setup(self):
            p = _rand((4, 3), 3)
            g = _rand((4, 3), 4)
            v = _rand((4, 3), 5)
            lr = np.array([0.05], 'float32')
            mu = 0.9
            v_out = mu * v + g
            if nesterov:
                p_out = p - (g + mu * v_out) * 0.05
            else:
                p_out = p - 0.05 * v_out
            self.inputs = {'Param': p, 'Grad': g, 'Velocity': v,
                           'LearningRate': lr}
            self.attrs = {'mu': mu, 'use_nesterov': nesterov}
            self.outputs = {'ParamOut': p_out, 'VelocityOut': v_out}
    T().check_output()


def test_adam():
    class T(OpTest):
        op_type = 'adam'

        def setup(self):
            p = _rand((4, 3), 6)
            g = _rand((4, 3), 7)
            m1 = _rand((4, 3), 8, 0, 1)
            m2 = _rand((4, 3), 9, 0, 1)
            lr = np.array([0.001], 'float32')
            b1, b2, eps = 0.9, 0.999, 1e-8
            b1p = np.array([b1 ** 3], 'float32')
            b2p = np.array([b2 ** 3], 'float32')
            m1o = b1 * m1 + (1 - b1) * g
            m2o = b2 * m2 + (1 - b2) * g * g
            lr_t = 0.001 * np.sqrt(1 - b2p) / (1 - b1p)
            p_out = p - lr_t * m1o / (np.sqrt(m2o) + eps)
            self.inputs = {'Param': p, 'Grad': g, 'Moment1': m1,
                           'Moment2': m2, 'LearningRate': lr,
                           'Beta1Pow': b1p, 'Beta2Pow': b2p}
            self.attrs = {'beta1': b1, 'beta2': b2, 'epsilon': eps}
            self.outputs = {'ParamOut': p_out.astype('float32'),
                            'Moment1Out': m1o, 'Moment2Out': m2o,
                            'Beta1PowOut': b1p * b1,
                            'Beta2PowOut': b2p * b2}
    T().check_output(atol=1e-5)


def test_adagrad():
    class T(OpTest):
        op_type = 'adagrad'

        def setup(self):
            p = _rand((4, 3), 10)
            g = _rand((4, 3), 11)
            m = _rand((4, 3), 12, 0, 1)
            lr = np.array([0.01], 'float32')
            eps = 1e-6
            mo = m + g * g
            p_out = p - 0.01 * g / (np.sqrt(mo) + eps)
            self.inputs = {'Param': p, 'Grad': g, 'Moment': m,
                           'LearningRate': lr}
            self.attrs = {'epsilon': eps}
            self.outputs = {'ParamOut': p_out, 'MomentOut': mo}
    T().check_output()


def test_rmsprop():
    class T(OpTest):
        op_type = 'rmsprop'

        def setup(self):
            p = _rand((4, 3), 13)
            g = _rand((4, 3), 14)
            ms = _rand((4, 3), 15, 0.1, 1)
            mom = _rand((4, 3), 16, 0, 0.5)
            lr = np.array([0.01], 'float32')
            rho, eps, mu = 0.95, 1e-6, 0.9
            mso = rho * ms + (1 - rho) * g * g
            momo = mu * mom + 0.01 * g / np.sqrt(mso + eps)
            p_out = p - momo
            self.inputs = {'Param': p, 'Grad': g, 'MeanSquare': ms,
                           'Moment': mom, 'LearningRate': lr}
            self.attrs = {'decay': rho, 'epsilon': eps, 'momentum': mu,
                          'centered': False}
            self.outputs = {'ParamOut': p_out, 'MeanSquareOut': mso,
                            'MomentOut': momo}
    T().check_output(atol=1e-5)


def test_adadelta():
    class T(OpTest):
        op_type = 'adadelta'

        def setup(self):
            p = _rand((4, 3), 17)
            g = _rand((4, 3), 18)
            eg = _rand((4, 3), 19, 0.1, 1)
            ex = _rand((4, 3), 20, 0.1, 1)
            rho, eps = 0.95, 1e-6
            ego = rho * eg + (1 - rho) * g * g
            upd = -np.sqrt((ex + eps) / (ego + eps)) * g
            exo = rho * ex + (1 - rho) * upd * upd
            self.inputs = {'Param': p, 'Grad': g, 'AvgSquaredGrad': eg,
                           'AvgSquaredUpdate': ex}
            self.attrs = {'rho': rho, 'epsilon': eps}
            self.outputs = {'ParamOut': p + upd, 'AvgSquaredGradOut': ego,
                            'AvgSquaredUpdateOut': exo}
    T().check_output(atol=1e-5)


def test_ftrl():
    class T(OpTest):
        op_type = 'ftrl'

        def setup(self):
            p = _rand((4, 3), 21)
            g = _rand((4, 3), 22)
            sq = _rand((4, 3), 23, 0.1, 1)
            lin = _rand((4, 3), 24)
            lr = np.array([0.01], 'float32')
            l1, l2, power = 0.1, 0.2, -0.5
            nsq = sq + g * g
            sigma = (nsq ** -power - sq ** -power) / 0.01
            lino = lin + g - sigma * p
            y = nsq ** -power / 0.01 + 2 * l2
            p_out = np.where(np.abs(lino) > l1,
                             (np.sign(lino) * l1 - lino) / y, 0.0)
            self.inputs = {'Param': p, 'Grad': g,
                           'SquaredAccumulator': sq,
                           'LinearAccumulator': lin, 'LearningRate': lr}
            self.attrs = {'l1': l1, 'l2': l2, 'lr_power': power}
            self.outputs = {'ParamOut': p_out.astype('float32'),
                            'SquaredAccumOut': nsq,
                            'LinearAccumOut': lino}
    T().check_output(atol=1e-4)


def test_decayed_adagrad_and_adamax():
    class D(OpTest):
        op_type = 'decayed_adagrad'

        def setup(self):
            p, g, m = _rand((3, 3), 25), _rand((3, 3), 26), \
                _rand((3, 3), 27, 0.1, 1)
            lr = np.array([0.01], 'float32')
            decay, eps = 0.95, 1e-6
            mo = decay * m + (1 - decay) * g * g
            self.inputs = {'Param': p, 'Grad': g, 'Moment': m,
                           'LearningRate': lr}
            self.attrs = {'decay': decay, 'epsilon': eps}
            self.outputs = {'ParamOut': p - 0.01 * g / (np.sqrt(mo) + eps),
                            'MomentOut': mo}
    D().check_output(atol=1e-5)

    class A(OpTest):
        op_type = 'adamax'

        def setup(self):
            p, g = _rand((3, 3), 28), _rand((3, 3), 29)
            m, inf = _rand((3, 3), 30, 0, 1), _rand((3, 3), 31, 0.1, 1)
            lr = np.array([0.002], 'float32')
            b1, b2, eps = 0.9, 0.999, 1e-8
            b1p = np.array([b1 ** 2], 'float32')
            mo = b1 * m + (1 - b1) * g
            info = np.maximum(b2 * inf, np.abs(g))
            lr_t = 0.002 / (1 - b1p)
            self.inputs = {'Param': p, 'Grad': g, 'Moment': m,
                           'InfNorm': inf, 'LearningRate': lr,
                           'Beta1Pow': b1p}
            self.attrs = {'beta1': b1, 'beta2': b2, 'epsilon': eps}
            self.outputs = {'ParamOut': (p - lr_t * mo / (info + eps)
                                         ).astype('float32'),
                            'MomentOut': mo, 'InfNormOut': info}
    A().check_output(atol=1e-5)


# ---------------------------------------------------------------------------
# adam: a dense matrix's update is a pass of its own over the finished
# gradient (an optimization barrier), everything else is left to XLA's
# fusion — and the values are the ones the plain expressions give

class _AdamOp(object):
    """The `adam` lowering's view of an op: slots name themselves."""

    def input(self, slot):
        return [slot]

    output = input

    def attr(self, name, default=None):
        return {'beta1': 0.9, 'beta2': 0.999, 'epsilon': 1e-8}.get(name,
                                                                   default)


class _Ctx(object):
    def __init__(self, env):
        self.env = env

    def in1(self, op, slot, default=None):
        return self.env.get(slot, default)

    def out(self, op, slot, value, idx=0):
        self.env[slot] = value


def _adam_case(kind):
    import jax.numpy as jnp
    from paddle_tpu.core.selected_rows import SelectedRows
    shape = {'matrix': (2048, 1024), 'cube': (2, 1024, 1024),
             'under the floor': (2047, 1024), 'vector': (7,),
             'rows': (6, 5)}[kind]
    p, m1 = jnp.asarray(_rand(shape, 10)), jnp.asarray(_rand(shape, 11))
    m2 = jnp.asarray(_rand(shape, 12, 0, 1))
    if kind == 'rows':
        g = SelectedRows(jnp.asarray([4, 1, 4], jnp.int32),
                         jnp.asarray(_rand((3, 5), 13)), 6)
    else:
        g = jnp.asarray(_rand(shape, 13))
    return p, g, m1, m2


@pytest.mark.parametrize('kind,form', [
    ('matrix', 'own_pass'), ('cube', 'own_pass'),
    ('under the floor', 'inline'), ('vector', 'inline'), ('rows', 'inline')])
def test_adam_update_form_and_values(kind, form):
    """The op's three outputs are bit for bit `_adam_dense`'s (a
    SelectedRows gradient: `_adam_sparse`'s) under one jit each, and
    `adam_update_form_total{form}` counts the call site once, at trace
    time: `own_pass` a dense gradient of rank >= 2 and 2 M elements,
    `inline` what the rule leaves to XLA (a smaller matrix, vectors,
    sparse rows)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import monitor
    from paddle_tpu.core.registry import _registry
    from paddle_tpu.ops import optimizer_ops as O
    p, g, m1, m2 = _adam_case(kind)
    lr = jnp.asarray([0.001], jnp.float32)
    b1p = jnp.asarray([0.9 ** 3], jnp.float32)
    b2p = jnp.asarray([0.999 ** 3], jnp.float32)

    def through_op(p, g, m1, m2):
        env = {'Param': p, 'Grad': g, 'Moment1': m1, 'Moment2': m2,
               'LearningRate': lr, 'Beta1Pow': b1p, 'Beta2Pow': b2p}
        _registry.get('adam').lower(_Ctx(env), _AdamOp())
        return env['ParamOut'], env['Moment1Out'], env['Moment2Out']

    def plain(p, g, m1, m2):
        lr_t = lr.reshape(()) * jnp.sqrt(1 - b2p.reshape(())) \
            / (1 - b1p.reshape(()))
        fn = O._adam_sparse if kind == 'rows' else O._adam_dense
        return fn(p, g, m1, m2, lr_t, 0.9, 0.999, 1e-8)

    before = monitor.counters()
    step = jax.jit(through_op)      # a SelectedRows is a pytree
    got = step(p, g, m1, m2)
    step(p, g, m1, m2)              # compiled: the second call counts nothing
    moved = {k: v for k, v in monitor.counter_delta(before).items()
             if k.startswith('adam_update_form_total')}
    assert moved == {'adam_update_form_total{form=%s}' % form: 1}, moved
    for a, b in zip(got, jax.jit(plain)(p, g, m1, m2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
