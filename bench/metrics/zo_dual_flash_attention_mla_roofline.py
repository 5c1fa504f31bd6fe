"""Share of its roofline of the fused dual-probe flash attention
(``kernels/flash_attention.zo_dual_flash_attention``) where the value
head dim differs from the query/key one (MLA: 192 and 128), over the
traced window, counted as ``zo_dual_flash_attention_roofline`` but with
``2 * 2 * (Dqk + Dv)`` FLOPs per head and causal query-key pair (two
streams; the scores over Dqk, the weighted sum over Dv), the dims read
from the q and v operands."""
from lib import kernels as K
from lib import roofline


def match(call):
    return call["wrapper"] == "zo_dual_flash_attention"


def work(call):
    # qa, qb (.., B*H, Sq, Dqk), k (.., B*Kv, Skv, Dqk), v (.., Skv, Dv)
    arr = K.arrays(call)
    q, k, v = arr[0][1], arr[2][1], arr[3][1]
    sq, skv = q[-2], k[-2]
    pairs = sq * (sq + 1) // 2 if sq == skv else sq * skv
    return {"flops": 2 * 2 * K.lead(q, 2) * pairs * (q[-1] + v[-1]),
            "bytes": K.io_bytes(call)}


def read(record):
    return roofline.share(record, match, work)
