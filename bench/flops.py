"""Operations of the detector, from the configuration's shapes alone."""
from __future__ import annotations


def conv_flops_per_frame(ssd: dict) -> int:
    """2 * H_out * W_out * k * k * C_in * C_out summed over the stride-2
    3x3 backbone convs and the two 3x3 heads, for one frame."""
    size, c_in, total, maps = ssd["image_size"], 3, 0, []
    for c in ssd["channels"]:
        size = -(-size // 2)
        total += 2 * size * size * 9 * c_in * c
        maps.append((size, c))
        c_in = c
    head = 2 * (4 + 1 + ssd["n_classes"])
    for size, c in maps[-2:]:
        total += 2 * size * size * 9 * c * head
    return total
