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
