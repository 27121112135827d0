"""models/torso.py NatureConv: 8x8/4 x32, 4x4/2 x64, 3x3/1 x64, VALID
padding, flattened."""

CONVS = ((32, 8, 4), (64, 4, 2), (64, 3, 1))  # (features, kernel, stride)


def macs(section: dict) -> tuple[int, int]:
    """(multiply-adds of the three convolutions for one frame, features out)."""
    if section.get("torso_width", 1) != 1:
        raise ValueError("torso_width other than 1 is not counted here")
    h, w, c = section["model_input"]
    total = 0
    for features, k, s in CONVS:
        h, w = (h - k) // s + 1, (w - k) // s + 1
        total += h * w * features * k * k * c
        c = features
    return total, h * w * c
