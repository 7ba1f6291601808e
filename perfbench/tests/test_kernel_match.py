"""Each kernel's ``match`` in ``perfbench/kernels`` finds its own calls and
no other kernel's: op texts as the compiler prints them for a TPU v5e (the
operands' shapes and the kernel's metadata; the serialized kernel body cut
out), at small shapes."""

import pytest

import harness

TAIL = ', backend_config={...}'
TEXTS = {
    "flash_attention": [
        # the forward: the output and the row statistics, a tuple
        'jvp_flash_attention_.1 = (f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)}, '
        'f32[1,16,8,1024]{3,2,1,0:T(8,128)S(1)}) custom-call('
        'f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)} copy, '
        'f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)} copy.1, '
        'f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)} copy.2), '
        'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={\n'
        '"kernel":"flash_attention"\n}}' + TAIL,
        # the vjp: dq, dk and dv
        'transpose_jvp_flash_attention_bwd__.1 = (f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)}, '
        'f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)}, f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)}) '
        'custom-call(f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)} copy, '
        'f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)} copy.1, '
        'f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)} copy.2, '
        'f32[1,16,1024,64]{3,2,1,0:T(8,128)S(1)} copy.7, '
        'f32[1,16,8,1024]{3,2,1,0:T(8,128)S(1)} pallas_call.9, '
        'f32[1,16,8,1024]{3,2,1,0:T(8,128)S(1)} broadcast_in_dim.7), '
        'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={\n'
        '"kernel":"flash_attention_bwd"\n}}' + TAIL,
    ],
    "emu_bank": [
        'emu_bank.1 = f32[1,256,128]{2,1,0:T(8,128)S(1)} custom-call('
        'f32[1,4,256,20]{3,2,1,0:T(8,128)S(1)} bitcast.11, '
        'f32[4,1,20,128]{3,2,1,0:T(8,128)S(1)} pad.13, s32[2,128]{1,0:T(2,128)S(1)} '
        'pad_add_fusion, s32[2]{0:T(128)S(1)} convert_element_type.11), '
        'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={\n'
        '"kernel":"emu_bank"\n}}' + TAIL,
    ],
    "moe_gmm": [
        'ROOT moe_gmm.1 = f32[1024,512]{1,0:T(8,128)} custom-call('
        's32[3]{0:T(128)S(1)} pad_add_fusion, s32[3]{0:T(128)S(1)} compare_select_fusion.15, '
        's32[3]{0:T(128)S(1)} compare_select_fusion.14, s32[1]{0:T(128)} bitcast.14, '
        'bf16[1024,256]{1,0:T(8,128)(2,1)S(1)} convert_element_type.1, '
        'f32[2,256,512]{2,1,0:T(8,128)S(1)} custom-call.6), '
        'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={\n'
        '"kernel":"moe_gmm"\n}}' + TAIL,
    ],
}
CASES = [(kernel, i) for kernel, texts in TEXTS.items() for i in range(len(texts))]


@pytest.mark.parametrize("kernel,i", CASES)
def test_each_call_is_found_by_its_own_match_only(kernel, i):
    text = TEXTS[kernel][i]
    found = [k for k in TEXTS
             if harness.load_py(harness.bench_file("kernels", k + ".py")).match(text)]
    assert found == [kernel]


def test_flash_attention_cost_counts_the_visible_pairs():
    fa = harness.load_py(harness.bench_file("kernels", "flash_attention.py"))
    call = {"heads": 128, "s": 1024, "dk": 64, "dv": 64, "causal": True, "kind": "fwd"}
    ops, nbytes = fa.cost(call)
    assert ops == 128 * (1024 * 1025 / 2) * 2 * 128
    full, _ = fa.cost({**call, "causal": False})
    assert full / ops == pytest.approx(2 * 1024 / 1025)
    bwd, bwd_bytes = fa.cost({**call, "kind": "bwd"})
    assert bwd / ops == pytest.approx(2.5) and bwd_bytes > nbytes
