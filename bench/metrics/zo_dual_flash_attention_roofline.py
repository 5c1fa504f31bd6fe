"""Share of its roofline of the fused dual-probe flash attention
(``kernels/flash_attention.zo_dual_flash_attention``) over the traced
window, counted as for the matmul; attention FLOPs are causal (a query
at position i attends i + 1 keys)."""
from lib import kernels as K
from lib import roofline


def match(call):
    return call["wrapper"] == "zo_dual_flash_attention"


def work(call):
    # qa, qb (.., B*H, Sq, D), k, v (.., B*Kv, Skv, D) [, kb, vb]
    arr = K.arrays(call)
    q, k = arr[0][1], arr[2][1]
    sq, d, skv = q[-2], q[-1], k[-2]
    pairs = sq * (sq + 1) // 2 if sq == skv else sq * skv
    return {"flops": 2 * 4 * K.lead(q, 2) * pairs * d,
            "bytes": K.io_bytes(call)}


def read(record):
    return roofline.share(record, match, work)
