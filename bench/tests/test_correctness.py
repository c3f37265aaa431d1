"""The harness end to end on the CPU at a test size, past its look for a
chip: a sound run is correct, and the control and each fault the cell can
have come out not correct.

- control: the program's own bfloat16 path, the nearest precision below the
  float32 the configuration states;
- a step that returns its state unchanged: the decode tick keeps the KV
  pool as it was, so what it decodes is never written;
- a token altered where it is produced: the tick's first row gets the next
  token id.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "smollm-360m-bika.chat"
SEED = 2**31 + 77


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def drive(**kw):
    return run.run_cell(CELL, SEED, 1.5, False, cfg=load("tiny-bika.json"),
                        traffic=load("tiny-chat.json"), limits=load("tiny-limits.json")["limits"],
                        require_tpu=False, **kw)


def state_unchanged(eng):
    sched, api = eng.scheduler, eng.api

    def tick(params, cache, tok, pos, tables):
        logits, _ = api.decode_paged(params, tok[:, None], cache, pos, tables)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

    sched._tick_fn = jax.jit(tick)


def token_altered(eng):
    sched, vocab = eng.scheduler, eng.arch.vocab
    inner = sched._tick_fn

    def tick(*a):
        nxt, cache = inner(*a)
        return nxt.at[0].set((nxt[0] + 1) % vocab), cache

    sched._tick_fn = tick


def test_a_sound_run_is_correct():
    out = drive()
    assert out["correct"], out["check"]
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("how", [
    dict(overrides={"compute_dtype": "bfloat16"}),
    dict(engine_hook=state_unchanged),
    dict(engine_hook=token_altered),
], ids=["control_bfloat16", "state_unchanged", "token_altered"])
def test_the_control_and_each_fault_are_not_correct(how):
    out = drive(**how)
    assert not out["correct"], out["check"]
    assert any(v["value"] > v["limit"] for v in out["check"].values())
