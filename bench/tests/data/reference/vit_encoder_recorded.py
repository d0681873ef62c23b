"""A reference that only the tests' directory holds: it records each
call and hands the work to ``vit_encoder``."""
from reference import vit_encoder

calls = []


def check_steps(config, traffic, seeds, rows, ids, **kw):
    calls.append(("check_steps", dict(config["sizes"]),
                  [[int(s) for s in i] for i in ids]))
    return vit_encoder.check_steps(config, traffic, seeds, rows, ids, **kw)


def train_flops_per_sample(sizes):
    calls.append(("train_flops_per_sample", dict(sizes)))
    return vit_encoder.train_flops_per_sample(sizes)
