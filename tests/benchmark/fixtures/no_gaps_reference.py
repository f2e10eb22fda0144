"""A reference module that breaks the contract (no ``served_gaps``): the
manifest test has to refuse a configuration whose file names it."""


def pad_to(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def logits(config, weights, token_ids, weight_bits=8):
    raise NotImplementedError
