"""Vector observations (the tests' CartPole-sized sections): the IMPALA
net's MLP([256], 256) / the R2D2 net's two Dense(256)."""


def macs(section: dict) -> tuple[int, int]:
    return section["model_input"][0] * 256 + 256 * 256, 256
