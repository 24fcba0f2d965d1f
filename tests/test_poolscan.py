"""tools/poolscan.py `scan`: which instructions of a compiled serving
program make a value as long as a pool. The lines are XLA:TPU's, cut from
the device-less compile of chat's 2-layer b64 prefill at PR 42 (parent:
``cache[:, layer][tables]``; change: `kv_cache_ops.pool_pages`)."""
import pytest

from tools import poolscan

POOL = (1024, 2, 16, 1024)
SLICE_BODY = '''\
%fused_computation.48 (param_0.1: f32[1024,2,16,1024]) -> bf16[1024,16,1024] {
  %param_0.1 = f32[1024,2,16,1024]{3,2,1,0:T(8,128)} parameter(0)
  %slice.3 = bf16[1024,1,16,1024]{3,2,1,0:T(8,128)(2,1)} slice(%param_0.1), slice={[0:1024], [1:2], [0:16], [0:1024]}
  ROOT %bitcast.2 = bf16[1024,16,1024]{2,1,0:T(8,128)(2,1)S(1)} bitcast(%slice.3)
}

'''
WRITE_BODY = '''\
%fused_computation.7 (param_0.21: f32[1024,2,16,1024], param_1.23: s32[64], param_2.9: f32[64,1024]) -> f32[1024,2,16,1024] {
  %param_0.21 = f32[1024,2,16,1024]{3,2,1,0:T(8,128)} parameter(0)
  ROOT %scatter.1 = f32[1024,2,16,1024]{3,2,1,0:T(8,128)} scatter(%param_0.21, %param_1.23, %param_2.9), to_apply=%region_0.1
}

'''
ENTRY = '''\
ENTRY %main.45 (rw_state__gen_kv_k__.1: f32[1024,2,16,1024], w: f32[1024,4096]) -> (s32[1,1], f32[1024,2,16,1024]) {
  %rw_state__gen_kv_k__.1 = f32[1024,2,16,1024]{3,2,1,0:T(8,128)} parameter(0)
  %w = f32[1024,4096]{1,0:T(8,128)} parameter(1)
  %copy.3 = f32[1024,4096]{0,1:T(8,128)} copy(%w)
  %fusion.7 = f32[1024,2,16,1024]{3,2,1,0:T(8,128)} fusion(%rw_state__gen_kv_k__.1, %gte.42, %reshape.311), kind=kCustom, calls=%fused_computation.7, metadata={op_name="jit(lm_prefill_paged)/scatter"}
BODY
}
'''
PARENT = ENTRY.replace('BODY', '''\
  %slice_bitcast_fusion.7 = bf16[1024,16,1024]{2,1,0:T(8,128)(2,1)S(1)} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.48, metadata={op_name="jit(lm_prefill_paged)/squeeze"}
  %gather.8 = bf16[48,16,1024]{2,1,0:T(8,128)(2,1)} gather(%slice_bitcast_fusion.7, %custom-call.1), offset_dims={1,2}, collapsed_slice_dims={0}, slice_sizes={1,16,1024}''')
CHANGE = ENTRY.replace('BODY', '''\
  %gather.8 = bf16[48,16,1024]{2,1,0:T(8,128)(2,1)} gather(%fusion.7, %custom-call.1), offset_dims={1,2}, collapsed_slice_dims={0,1}, slice_sizes={1,1,16,1024}''')
WRITES = {'fusion fusion f32[1024, 2, 16, 1024]': 1}


@pytest.mark.parametrize('text,share', [
    (SLICE_BODY + WRITE_BODY + PARENT, {'fusion slice_bitcast_fusion bf16[1024, 16, 1024]': 1}),
    (WRITE_BODY + CHANGE, {}),
    # a slice that is its own instruction, the layer's axis kept
    (WRITE_BODY + CHANGE.replace('%gather.8 = ', '''\
%slice.9 = f32[1024,1,16,1024]{3,2,1,0} slice(%fusion.7), slice={[0:1024], [1:2], [0:16], [0:1024]}
  %gather.8 = '''), {'slice slice f32[1024, 1, 16, 1024]': 1}),
], ids=['slice-then-gather', 'one-gather', 'bare-slice'])
def test_scan_finds_a_layers_share_made_anew_and_the_page_writes(text, share):
    """Instructions inside a fusion's body (the `slice` and `bitcast` of
    `fused_computation.48`, the `scatter` of `.7`), parameters, and a
    weight whose leading dimension happens to be `num_blocks` are not
    counted; the in-place writes are, under the pool's own shape."""
    assert poolscan.scan(text, [POOL], 16) == (share, WRITES)


def test_scan_tells_two_pools_apart_by_their_widths():
    pools = [POOL, (1024, 2, 16, 512)]
    share, whole = poolscan.scan(SLICE_BODY + WRITE_BODY + PARENT, pools[1:], 16)
    assert (share, whole) == ({}, {})
    assert poolscan.scan(SLICE_BODY + WRITE_BODY + PARENT, pools, 16)[1] == WRITES


# tools/boundlayouts.py `weight_copies`: the lines are XLA:TPU's, cut from
# the device-less compile of one ungated `moe_ffn` at Nemotron's widths
# with the default entry (PR 49's parent): the up matrices, 2688 on the
# lanes by default, transposed in front of the grouped matmul in each
# branch of `grouped_ffn`'s conditional.
BRANCH = '''\
%%branch_%d_fun.%d (arg_tuple.%d: (s32[768], f32[128,2688], f32[16,2688,1856], s32[16], f32[16,1856,2688])) -> f32[768,2688] {
  %%arg_tuple.%d = (s32[768]{0:T(1024)}, f32[128,2688]{1,0:T(8,128)}, f32[16,2688,1856]{1,2,0:T(8,128)}, s32[16]{0:T(128)S(1)}, f32[16,1856,2688]{2,1,0:T(8,128)}) parameter(0)
  %%get-tuple-element.5%d = f32[16,2688,1856]{1,2,0:T(8,128)} get-tuple-element(%%arg_tuple.%d), index=2
  %%copy.%d = f32[16,2688,1856]{2,1,0:T(8,128)} copy(%%get-tuple-element.5%d), backend_config={"flag_configs":[]}
  %%copy.1%d = s32[128,128]{1,0:T(8,128)S(1)} copy(%%get-tuple-element.6%d)
}

'''
MOE_ENTRY = '''\
ENTRY %main.18 (x: f32[128,2688], w_up: f32[16,2688,1856], w_down: f32[16,1856,2688]) -> f32[128,2688] {
  %w_up = f32[16,2688,1856]{1,2,0:T(8,128)} parameter(1)
  %w_down = f32[16,1856,2688]{2,1,0:T(8,128)} parameter(2)
}
'''


class _Leaf(object):
    def __init__(self, *shape):
        import numpy as np
        self.shape, self.dtype = shape, np.dtype('float32')


@pytest.mark.parametrize('branches,least,want', [
    (2, 1 << 20, {'f32[16, 2688, 1856]': 2}),
    (0, 1 << 20, {}),
    # the small copies count once nothing is too small to be a weight
    (1, 0, {'f32[16, 2688, 1856]': 1}),
], ids=['both-branches', 'bound-entry', 'one-branch'])
def test_weight_copies_finds_a_weight_laid_out_anew(branches, least, want):
    from tools import boundlayouts
    text = ''.join(BRANCH % ((i,) * 10) for i in range(branches)) + MOE_ENTRY
    leaves = [_Leaf(2688, 128), _Leaf(16, 2688, 1856),
              _Leaf(16, 1856, 2688)]
    assert boundlayouts.weight_copies(text, leaves, least) == want
