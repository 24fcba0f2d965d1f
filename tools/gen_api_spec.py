"""Generate API.spec: the frozen public-API signature list (the reference
CI gate paddle/fluid/API.spec checked by tools/diff_api.py). Run from the
repo root to regenerate after an INTENTIONAL API change:

    JAX_PLATFORMS=cpu python tools/gen_api_spec.py > API.spec
"""
import inspect
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _spec_of(fn):
    import re
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return '(unsignaturable)'
    # object reprs embed per-process addresses and private module paths
    # (both unstable across processes/jax versions) — normalize them
    out = re.sub(r' at 0x[0-9a-f]+', '', str(sig))
    return re.sub(r'<[\w\.]+ object>', '<object>', out)


def iter_api():
    import jax
    # the spec needs no device: never take a chip for it
    jax.config.update('jax_platforms', 'cpu')
    import paddle_tpu as fluid

    modules = [
        ('paddle_tpu', fluid),
        ('paddle_tpu.layers', fluid.layers),
        ('paddle_tpu.layers.detection', fluid.layers.detection),
        ('paddle_tpu.optimizer', fluid.optimizer),
        ('paddle_tpu.initializer', fluid.initializer),
        ('paddle_tpu.regularizer', fluid.regularizer),
        ('paddle_tpu.clip', fluid.clip),
        ('paddle_tpu.metrics', fluid.metrics),
        ('paddle_tpu.monitor', fluid.monitor),
        ('paddle_tpu.trace', fluid.trace),
        ('paddle_tpu.analysis', fluid.analysis),
        ('paddle_tpu.goodput', fluid.goodput),
        ('paddle_tpu.health', fluid.health),
        ('paddle_tpu.blackbox', fluid.blackbox),
        ('paddle_tpu.resilience', fluid.resilience),
        ('paddle_tpu.evaluator', fluid.evaluator),
        ('paddle_tpu.compat', fluid.compat),
        ('paddle_tpu.net_drawer', fluid.net_drawer),
        ('paddle_tpu.default_scope_funcs', fluid.default_scope_funcs),
        ('paddle_tpu.contrib.reader', fluid.contrib.reader),
        ('paddle_tpu.io', fluid.io),
        ('paddle_tpu.nets', fluid.nets),
        ('paddle_tpu.reader', fluid.reader),
        ('paddle_tpu.imperative', fluid.imperative),
        ('paddle_tpu.contrib.slim', fluid.contrib.slim),
        ('paddle_tpu.parallel', fluid.parallel),
        ('paddle_tpu.serving', fluid.serving),
        ('paddle_tpu.ps', fluid.ps),
        ('paddle_tpu.distributed.launch',
         __import__('paddle_tpu.distributed.launch',
                    fromlist=['launch'])),
    ]
    rows = []
    for mod_name, mod in modules:
        names = getattr(mod, '__all__', None)
        if names is None:
            names = [n for n in dir(mod) if not n.startswith('_')
                     and (inspect.isfunction(getattr(mod, n))
                          or inspect.isclass(getattr(mod, n)))]
        for name in sorted(names):
            obj = getattr(mod, name, None)
            if obj is None:
                continue
            if getattr(obj, '__module__', None) == 'builtins':
                rows.append('%s.%s <builtin alias>' % (mod_name, name))
                continue
            if inspect.isclass(obj):
                rows.append('%s.%s.__init__ %s' % (
                    mod_name, name, _spec_of(obj.__init__)))
                for meth in sorted(vars(obj)):
                    if meth.startswith('_'):
                        continue
                    m = getattr(obj, meth)
                    if callable(m):
                        rows.append('%s.%s.%s %s' % (
                            mod_name, name, meth, _spec_of(m)))
            elif callable(obj):
                rows.append('%s.%s %s' % (mod_name, name, _spec_of(obj)))
    return rows


if __name__ == '__main__':
    for row in iter_api():
        sys.stdout.write(row + '\n')
