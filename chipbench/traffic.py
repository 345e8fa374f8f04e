"""The one traffic generator: reads a mix's parameters and draws every
request from ``--seed``.

A mix (``traffic/<name>.json``) gives the loop (``closed``: each client
sends its next request when its last image arrives), the number of
clients (``clients``, or ``clients_per_batch_cap`` times the
configuration's batch cap) and the prompt length in words
(``prompt_words``: [least, most]).  Steps and guidance are the
configuration's, from the model card, unless the mix gives ``steps`` or
``guidance`` itself.

Every request has the same shapes (prompts are padded to the
configuration's ``text_tokens``), so seeds change the order and content of
the work, never its amount.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator

import numpy as np

WORDS = (
    "a an the of in on at with under over portrait landscape photo painting "
    "watercolor oil sketch render isometric cinematic studio lighting dusk "
    "dawn night snowy forest desert ocean city street market harbor mountain "
    "river lake garden castle lighthouse bridge train robot fox cat dog owl "
    "horse dragon astronaut chef dancer violinist old young smiling red blue "
    "golden silver misty foggy rainy sunny detailed sharp soft bokeh wide "
    "close macro aerial view neon vintage minimalist baroque futuristic"
).split()


@dataclasses.dataclass(frozen=True)
class Mix:
    clients: int
    prompt_words: tuple
    steps: int
    guidance: float


def mix_from(traffic: Dict[str, Any], config: Dict[str, Any]) -> Mix:
    if traffic["loop"] != "closed":
        raise ValueError(f"loop {traffic['loop']!r}: only 'closed' is built")
    clients = traffic.get("clients")
    if clients is None:
        clients = traffic["clients_per_batch_cap"] * config["batch_cap"]
    lo, hi = traffic["prompt_words"]
    return Mix(clients=int(clients), prompt_words=(lo, hi),
               steps=int(traffic.get("steps", config["steps"])),
               guidance=float(traffic.get("guidance", config["guidance"])))


def requests(mix: Mix, seed: int) -> Iterator[Dict[str, Any]]:
    """The request inputs, in order: a latent seed and a prompt each."""
    rng = np.random.default_rng(int(seed))
    lo, hi = mix.prompt_words
    while True:
        n = int(rng.integers(lo, hi + 1))
        words = rng.choice(len(WORDS), size=n)
        yield {"seed": int(rng.integers(0, 2**31 - 1)),
               "prompt": " ".join(WORDS[i] for i in words)}
