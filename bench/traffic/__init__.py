"""Traffic mixes: ``<mix>.json`` holds a mix's parameters and names its
generator ``kind``; ``<kind>.py`` is that generator, found by name.

A generator module exposes ``make(mix, seed, seconds, vocab)``, returning an
object with ``ramp_s``, ``closed``, ``requests`` (every request it may
send), ``initial()`` (the requests scheduled up front, each with its due
time in seconds from the start of traffic) and ``on_complete(req, t_s)``
(the request a completion releases, or None).
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
from typing import Optional

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Request:
    rid: int
    due_s: Optional[float]
    prompt_len: int
    max_new: int
    client: int = 0


def load_mix(name: str, directory: pathlib.Path = HERE) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


def generator(kind: str):
    return importlib.import_module(f"bench.traffic.{kind}")
