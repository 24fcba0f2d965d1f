"""Parameter initializers — append init ops to the startup program.

Capability parity with reference python/paddle/fluid/initializer.py (Constant,
Uniform, Normal, TruncatedNormal, Xavier, MSRA, Bilinear, NumpyArray).
Initializers are ops in the startup program, so `exe.run(startup_program)`
materializes all parameters on device in one compiled XLA program.
"""
import numpy as np

__all__ = [
    'Constant', 'Uniform', 'Normal', 'TruncatedNormal', 'Xavier', 'MSRA',
    'Bilinear', 'NumpyArrayInitializer', 'ConstantInitializer',
    'UniformInitializer', 'NormalInitializer', 'TruncatedNormalInitializer',
    'XavierInitializer', 'MSRAInitializer', 'BilinearInitializer',
    'force_init_on_cpu', 'init_on_cpu',
]


def force_init_on_cpu():
    return False


import contextlib


@contextlib.contextmanager
def init_on_cpu():
    yield


class Initializer(object):
    def __call__(self, var, block):
        raise NotImplementedError


class ConstantInitializer(Initializer):
    def __init__(self, value=0.0, force_cpu=False):
        self._value = value

    def __call__(self, var, block):
        return block.append_op(
            type='fill_constant',
            outputs={'Out': [var.name]},
            attrs={'shape': list(var.shape), 'dtype': var.dtype,
                   'value': float(self._value)})


class UniformInitializer(Initializer):
    def __init__(self, low=-1.0, high=1.0, seed=0):
        self._low, self._high, self._seed = low, high, seed

    def __call__(self, var, block):
        return block.append_op(
            type='uniform_random',
            outputs={'Out': [var.name]},
            attrs={'shape': list(var.shape), 'dtype': var.dtype,
                   'min': self._low, 'max': self._high, 'seed': self._seed})


class NormalInitializer(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self._mean, self._std, self._seed = loc, scale, seed

    def __call__(self, var, block):
        return block.append_op(
            type='gaussian_random',
            outputs={'Out': [var.name]},
            attrs={'shape': list(var.shape), 'dtype': var.dtype,
                   'mean': self._mean, 'std': self._std, 'seed': self._seed})


class TruncatedNormalInitializer(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self._mean, self._std, self._seed = loc, scale, seed

    def __call__(self, var, block):
        return block.append_op(
            type='truncated_gaussian_random',
            outputs={'Out': [var.name]},
            attrs={'shape': list(var.shape), 'dtype': var.dtype,
                   'mean': self._mean, 'std': self._std, 'seed': self._seed})


def _fans(var):
    shape = var.shape
    if len(shape) == 2:
        return shape[0], shape[1]
    if len(shape) >= 3:
        receptive = int(np.prod(shape[2:]))
        return shape[1] * receptive, shape[0] * receptive
    return shape[0], shape[0]


class XavierInitializer(Initializer):
    def __init__(self, uniform=True, fan_in=None, fan_out=None, seed=0):
        self._uniform = uniform
        self._fan_in, self._fan_out, self._seed = fan_in, fan_out, seed

    def __call__(self, var, block):
        fi, fo = _fans(var)
        fi = self._fan_in if self._fan_in is not None else fi
        fo = self._fan_out if self._fan_out is not None else fo
        if self._uniform:
            limit = float(np.sqrt(6.0 / (fi + fo)))
            return UniformInitializer(-limit, limit, self._seed)(var, block)
        std = float(np.sqrt(2.0 / (fi + fo)))
        return NormalInitializer(0.0, std, self._seed)(var, block)


class MSRAInitializer(Initializer):
    def __init__(self, uniform=True, fan_in=None, seed=0):
        self._uniform, self._fan_in, self._seed = uniform, fan_in, seed

    def __call__(self, var, block):
        fi, _ = _fans(var)
        fi = self._fan_in if self._fan_in is not None else fi
        if self._uniform:
            limit = float(np.sqrt(6.0 / fi))
            return UniformInitializer(-limit, limit, self._seed)(var, block)
        std = float(np.sqrt(2.0 / fi))
        return NormalInitializer(0.0, std, self._seed)(var, block)


class BilinearInitializer(Initializer):
    """Bilinear upsample kernel for conv_transpose (reference
    initializer.py Bilinear)."""

    def __call__(self, var, block):
        shape = var.shape  # (C_in, C_out, kh, kw) or (C, 1, kh, kw)
        if len(shape) != 4:
            raise ValueError("Bilinear initializer needs 4-D weights")
        weight = np.zeros(shape, dtype='float32')
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(np.prod(shape[2:]))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            v = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
            weight[:, :, y, x] = v
        return block.append_op(
            type='assign_value',
            outputs={'Out': [var.name]},
            attrs={'shape': list(shape), 'dtype': var.dtype,
                   'values': weight.flatten().tolist()})


class NumpyArrayInitializer(Initializer):
    def __init__(self, value):
        self._value = np.asarray(value)

    def __call__(self, var, block):
        return block.append_op(
            type='assign_value',
            outputs={'Out': [var.name]},
            attrs={'shape': list(self._value.shape), 'dtype': var.dtype,
                   'values': self._value.flatten().tolist()})


class RowsInitializer(Initializer):
    """A matrix whose row ``i`` is ``values[i]`` throughout (Mamba's
    ``A_log``: one number a state, every channel alike): the column as an
    `assign_value`, tiled over the columns — the program carries
    ``len(values)`` numbers, not the matrix."""

    def __init__(self, values):
        self._values = np.asarray(values).reshape(-1)

    def __call__(self, var, block):
        column = block.create_var(name=var.name + '.column',
                                  shape=(len(self._values), 1),
                                  dtype=var.dtype)
        block.append_op(
            type='assign_value', outputs={'Out': [column.name]},
            attrs={'shape': [len(self._values), 1], 'dtype': var.dtype,
                   'values': self._values.tolist()})
        return block.append_op(
            type='expand', inputs={'X': [column.name]},
            outputs={'Out': [var.name]},
            attrs={'expand_times': [1, int(var.shape[1])]})


Constant = ConstantInitializer
Uniform = UniformInitializer
Normal = NormalInitializer
TruncatedNormal = TruncatedNormalInitializer
Xavier = XavierInitializer
MSRA = MSRAInitializer
Bilinear = BilinearInitializer
