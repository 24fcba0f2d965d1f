"""Worker for the multi-process multi-host tests (the reference
unittests/test_dist_base.py trainer-subprocess pattern, nccl2 mode).

Two entry modes:
- argv: python multihost_worker.py <coordinator> <nproc> <pid>
- launcher env (paddle_tpu.distributed.launch contract): no argv; rank /
  world / coordinator come from PADDLE_* env vars via init_from_env().

Each process owns MH_LOCAL_DEVICES (default 2) virtual CPU devices; the
global mesh spans nproc * local devices. MH_MODE selects the parallelism:
'dp' (CompiledProgram data parallel) or 'dp_tp' (MeshRunner over a
data x model mesh). Prints per-step losses as JSON on the last line.
"""
import json
import os
import sys

os.environ['JAX_PLATFORMS'] = 'cpu'
_local = int(os.environ.get('MH_LOCAL_DEVICES', '2'))
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=%d'
        % _local).strip()

import jax

import numpy as np


def _build():
    import paddle_tpu as fluid
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 23
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data(name='x', shape=[8], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, size=16, act='relu')
        p = fluid.layers.fc(h, size=4, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main_p, startup, loss


def main():
    import paddle_tpu as fluid
    if len(sys.argv) > 1:
        coordinator, nproc, pid = (sys.argv[1], int(sys.argv[2]),
                                   int(sys.argv[3]))
        from paddle_tpu.parallel import collective
        collective.init_distributed(coordinator_address=coordinator,
                                    num_processes=nproc, process_id=pid)
    else:
        from paddle_tpu.distributed import init_from_env
        pid, nproc = init_from_env()
    assert jax.process_count() == nproc
    assert jax.device_count() == _local * nproc

    main_p, startup, loss = _build()
    exe = fluid.Executor()
    exe.run(startup)

    # deterministic global batch, split by process (reference: each
    # trainer reads its own slice)
    rng = np.random.RandomState(5)
    per = 32 // nproc
    X = rng.randn(32, 8).astype('float32')
    Y = rng.randint(0, 4, (32, 1)).astype('int64')
    lo, hi = pid * per, (pid + 1) * per

    mode = os.environ.get('MH_MODE', 'dp')
    losses = []
    if mode == 'pipe':
        # pipeline parallelism ACROSS processes: mesh('pipe', 4) spans
        # both workers' devices, so each gpipe_run microbatch ppermute
        # crosses the process boundary (the multi-host analog of the
        # reference's pipeline trainers; section-per-device
        # pipeline_trainer). Serial reference computed locally — both
        # processes build identical programs/feeds from shared seeds.
        from paddle_tpu.parallel import make_mesh, MeshRunner
        from paddle_tpu.models.transformer import build_lm, LMConfig
        cfg = LMConfig(vocab_size=64, seq_len=8, d_model=16, n_head=2,
                       n_layer=4, d_ff=32, dropout=0.0, attn_dropout=0.0,
                       use_flash_attention=False)

        def _lm_prog():
            mp, sp = fluid.Program(), fluid.Program()
            mp.random_seed = sp.random_seed = 31
            with fluid.program_guard(mp, sp):
                tokens, labels, logits, avg_loss = build_lm(cfg)
                fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_loss)
            return mp, sp, avg_loss

        rngp = np.random.RandomState(6)
        pfeeds = [{'tokens': rngp.randint(
                       0, cfg.vocab_size, (8, cfg.seq_len)).astype('int64'),
                   'labels': rngp.randint(
                       0, cfg.vocab_size, (8, cfg.seq_len)).astype('int64')}
                  for _ in range(3)]
        mp1, sp1, l1 = _lm_prog()
        sref = fluid.Scope()
        with fluid.scope_guard(sref):
            exe.run(sp1, scope=sref)
            ref = [float(np.asarray(exe.run(
                       mp1, feed=f, fetch_list=[l1], scope=sref)[0]
                   ).reshape(())) for f in pfeeds]
        mp2, sp2, l2 = _lm_prog()
        ndev = jax.device_count()
        if os.environ.get('MH_PIPE_DP'):
            # dp-composed pipeline with the PIPE axis outermost: devices
            # are ordered by process, so pipe stage pairs land in
            # DIFFERENT processes — every stage-to-stage ppermute crosses
            # the process boundary (DCN in a real topology) while the
            # batch shards over 'data' (gpipe_run auto-engages
            # batch_axis)
            from jax.sharding import PartitionSpec as P
            pp = ndev // 2
            fluid.transpiler.PipelineTranspiler().transpile(
                mp2, num_stages=pp)
            mesh = make_mesh([('pipe', pp), ('data', 2)])
            runner = MeshRunner(mp2, mesh,
                                feed_specs={'tokens': P('data'),
                                            'labels': P('data')})
        else:
            fluid.transpiler.PipelineTranspiler().transpile(
                mp2, num_stages=ndev)
            mesh = make_mesh([('pipe', ndev)])
            runner = MeshRunner(mp2, mesh)
        s2 = fluid.Scope()
        with fluid.scope_guard(s2):
            exe.run(sp2, scope=s2)
            got = [float(np.asarray(runner.run(
                       f, [l2.name], s2)[0]).reshape(()))
                   for f in pfeeds]
        print("LOSSES:" + json.dumps({'ref': ref, 'pipe': got}))
        return
    if mode == 'ckpt':
        # kill-and-resume drill (reference io.py
        # _save_distributed_persistables + unittests/dist_save_load.py):
        # Reduce-mode DP (ZeRO-style sharded param/optimizer state),
        # orbax sharded checkpoint mid-run.
        #   ref:    4 uninterrupted steps
        #   crash:  2 steps -> save -> 1 more (un-checkpointed) step ->
        #           abnormal death (os._exit(17))
        #   resume: fresh cluster restores the checkpoint and runs steps
        #           3-4 — must match ref[2:]
        phase = os.environ['MH_CKPT_PHASE']
        ckpt_dir = os.environ['MH_CKPT_DIR']
        bs = fluid.BuildStrategy()
        bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
        compiled = fluid.CompiledProgram(main_p).with_data_parallel(
            loss_name=loss.name, build_strategy=bs)

        def step():
            l, = exe.run(compiled, feed={'x': X[lo:hi], 'y': Y[lo:hi]},
                         fetch_list=[loss])
            return float(np.asarray(l).reshape(()))

        if phase == 'ref':
            losses = [step() for _ in range(4)]
        elif phase == 'crash':
            losses = [step() for _ in range(2)]
            fluid.checkpoint.save_checkpoint(ckpt_dir, main_p)
            step()                      # advances PAST the checkpoint
            sys.stdout.flush()
            os._exit(17)                # die abnormally mid-run
        else:                           # resume
            restored = fluid.checkpoint.load_checkpoint(ckpt_dir, main_p)
            assert restored, "nothing restored"
            losses = [step() for _ in range(2)]
        print("LOSSES:" + json.dumps(losses))
        return
    if mode == 'dp':
        compiled = fluid.CompiledProgram(main_p).with_data_parallel(
            loss_name=loss.name)
        for _ in range(4):
            l, = exe.run(compiled, feed={'x': X[lo:hi], 'y': Y[lo:hi]},
                         fetch_list=[loss])
            losses.append(float(np.asarray(l).reshape(())))
    else:  # dp_tp: explicit data x model mesh spanning all hosts
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.parallel import make_mesh, MeshRunner, ShardingRules
        ndev = jax.device_count()
        tp = 2
        dp = ndev // tp
        mesh = make_mesh([('data', dp), ('model', tp)])
        rules = ShardingRules([
            (r'fc_0\.w', P(None, 'model')),
            (r'fc_0\.b', P('model',)),
            (r'fc_1\.w', P('model', None)),
        ])
        runner = MeshRunner(main_p, mesh, param_rules=rules,
                            feed_specs={'x': P('data'), 'y': P('data')})
        scope = fluid.global_scope()
        for _ in range(4):
            l, = runner.run({'x': X[lo:hi], 'y': Y[lo:hi]}, [loss.name],
                            scope)
            losses.append(float(np.asarray(l).reshape(-1)[0]))
    print("LOSSES:" + json.dumps(losses))


if __name__ == '__main__':
    main()
