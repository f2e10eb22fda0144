"""The engine's ByteTokenizer, restated so that the comparison that decides
``correct`` needs nothing of the program and belongs to no model family:
BOS, then one id per UTF-8 byte offset by the specials."""

from __future__ import annotations

BOS_ID, EOS_ID, BYTE_OFFSET = 1, 2, 3


def prompt_ids(prompt: str) -> list[int]:
    return [BOS_ID] + [b + BYTE_OFFSET for b in prompt.encode("utf-8")]
