"""What a change that claims to change nothing has to leave as it was: the
lowered text of every tiny preset's serving programs and of the trainer's
step, and every preset's seeded parameter tree. CPU, float32.

One table. A row is `<preset>:<kind>:<shape key>` (a warm-up program of the
preset's engine, sha256 of `LLMEngine.program_text`), `tiny:train_step` (the
lowered text of `ShardedTrainer`'s step under remat_policy="dots") or
`<preset>:params` (sha256 over the sorted (path, shape, dtype, bytes) of the
engine's parameters at its seed: chipbench/weights_*.py and the references
read a leaf by its path, and flax derives a leaf's key from it). A preset's
engine is built once. `PYTHONPATH=. JAX_PLATFORMS=cpu python
tests/test_program_pins.py [preset ...]` prints the rows of the tree at hand.

A row is read on the PARENT of the PR that changes a program on purpose, and
that PR says which rows it read again and why; no other PR touches a value.
"""

import hashlib

import jax
import numpy as np
import pytest

from ray_tpu.serve.llm import EngineConfig, LLMEngine

CFG = dict(dtype="float32", page_size=16, num_pages=64, max_model_len=256,
           max_batch=4)
# preset -> (prefill buckets, whether the verify program is held too)
PRESETS = {
    "tiny": ((32, 64, 128), True),
    "tiny-moe": ((32, 64, 128), True),
    "tiny-jamba": ((32, 64, 128), False),
    "tiny-sala": ((32, 64, 128), False),
    "tiny-sdar": ((32, 64, 128), False),
    "tiny-kimi": ((32, 64), False),
    "tiny-mellum": ((32, 64), False),
    "tiny-gigachat": ((32, 64), False),
    "tiny-laguna": ((32, 64), False),
    "tiny-zaya": ((32, 64), False),
}

PINS = {
    # Mistral's, Mixtral's, Jamba's and MiniCPM-SALA's stacks: read on PR
    # 39's tree; the prefill and decode programs again on PR 51's, which put
    # their sampler behind a `cond` (the two verify programs kept PR 39's)
    "tiny:prefill:(32, 2, 0)": "b1bbc3b602c58be8",
    "tiny:prefill:(32, 2, 16)": "ed8fac0284a19140",
    "tiny:prefill:(64, 2, 0)": "5993678cc15c6fc3",
    "tiny:prefill:(64, 2, 16)": "9282c84ec16f1900",
    "tiny:prefill:(128, 2, 0)": "4379d01c041118bc",
    "tiny:prefill:(128, 2, 16)": "94baa2a1e370f7ab",
    "tiny:decode:(1, 16)": "84c7bb57bc9d2302",
    "tiny:verify:(32, 2)": "bbfb32dae0dd51a5",
    "tiny-moe:prefill:(32, 2, 0)": "aad31e4b220d84ff",
    "tiny-moe:prefill:(32, 2, 16)": "1c775b7a829f1c7c",
    "tiny-moe:prefill:(64, 2, 0)": "d9eb1dc335cbbd67",
    "tiny-moe:prefill:(64, 2, 16)": "2520101460321120",
    "tiny-moe:prefill:(128, 2, 0)": "c34a7908099cb9f8",
    "tiny-moe:prefill:(128, 2, 16)": "c926e74572eb0be0",
    "tiny-moe:decode:(1, 16)": "47a33e1f46d6abd6",
    "tiny-moe:verify:(32, 2)": "0aea2088f65e850a",
    "tiny-jamba:prefill:(32, 2, 0)": "d2eceb49f016142e",
    "tiny-jamba:prefill:(64, 2, 0)": "e7c0816dd90d7a20",
    "tiny-jamba:prefill:(128, 2, 0)": "010b1a817f8f6e9a",
    "tiny-jamba:decode:(1, 16)": "ee4efac34bc231f1",
    "tiny-sala:prefill:(32, 2, 0)": "20c5b1384f375e48",
    "tiny-sala:prefill:(32, 2, 16)": "b2921113f4ec6f9d",
    "tiny-sala:prefill:(64, 2, 0)": "de8910385b4113af",
    "tiny-sala:prefill:(64, 2, 16)": "91b4739720363ade",
    "tiny-sala:prefill:(128, 2, 0)": "a4d5ccd27411748c",
    "tiny-sala:decode:(1, 16)": "190a38380eedc95a",
    # SDAR's: the prefill programs on PR 51's tree; the block program, with
    # a sampler of its own, on PR 54's, which put the head and the decision
    # of a pass behind one `cond`
    "tiny-sdar:prefill:(32, 2, 0)": "d754dafa5b91be7b",
    "tiny-sdar:prefill:(32, 2, 16)": "1515272d01e5cdf9",
    "tiny-sdar:prefill:(64, 2, 0)": "8e6d20790b1d2513",
    "tiny-sdar:prefill:(64, 2, 16)": "4caef83d460e013a",
    "tiny-sdar:prefill:(128, 2, 0)": "8af33472245683d3",
    "tiny-sdar:prefill:(128, 2, 16)": "3b68f1965e23582d",
    "tiny-sdar:block:(4, 4, 16)": "779b5505889ed6ce",
    # Kimi's and Mellum's: read on PR 49's tree, again on PR 51's
    "tiny-kimi:prefill:(32, 2, 0)": "294666d94664b830",
    "tiny-kimi:prefill:(32, 2, 16)": "6354526a6b850643",
    "tiny-kimi:prefill:(64, 2, 0)": "f7bffcbcf1c7c152",
    "tiny-kimi:prefill:(64, 2, 16)": "be045149bdeab1b4",
    "tiny-kimi:decode:(1, 16)": "508614620678e52b",
    "tiny-mellum:prefill:(32, 2, 0)": "d1c20523784a7a52",
    "tiny-mellum:prefill:(32, 2, 16)": "658bc43c2bb9b85e",
    "tiny-mellum:prefill:(64, 2, 0)": "71fe110de3ffcb23",
    "tiny-mellum:prefill:(64, 2, 16)": "ab342b3fd88253fb",
    "tiny-mellum:decode:(1, 16)": "7c6744cb80460bde",
    # everything below: read on PR 60's tree (PR 61's parent), before
    # models/_stack.py took the families' shared skeleton
    "tiny:train_step": "a90d93d5e194a374",
    "tiny-sala:prefill:(128, 2, 16)": "a4d5ccd27411748c",
    "tiny-gigachat:prefill:(32, 2, 0)": "34bac43970a53408",
    "tiny-gigachat:prefill:(32, 2, 16)": "b8f930a8c600f782",
    "tiny-gigachat:prefill:(64, 2, 0)": "5dc11aaeda584505",
    "tiny-gigachat:prefill:(64, 2, 16)": "f9c26822643f440b",
    "tiny-gigachat:decode:(1, 16)": "dade8e226442598e",
    "tiny-laguna:prefill:(32, 2, 0)": "9629cad1ea16a749",
    "tiny-laguna:prefill:(32, 2, 16)": "8e4f373a66431c36",
    "tiny-laguna:prefill:(64, 2, 0)": "f970cf0ea7bbc093",
    "tiny-laguna:prefill:(64, 2, 16)": "5b269b841383ecae",
    "tiny-laguna:decode:(1, 16)": "4e2f726bd1be4e4d",
    "tiny-zaya:prefill:(32, 2, 0)": "8e9030fc97eb0399",
    "tiny-zaya:prefill:(32, 2, 16)": "db80f57094bdd46c",
    "tiny-zaya:prefill:(64, 2, 0)": "30a84cf704e1b4ed",
    "tiny-zaya:prefill:(64, 2, 16)": "f0a1bb69ad6c71c9",
    "tiny-zaya:decode:(1, 16)": "520499226e08382f",
    # a preset's seeded parameters: (path, shape, dtype, bytes), sorted
    "tiny:params": "98753c759e9fc96b",
    "tiny-moe:params": "2734bf8389c873d9",
    "tiny-jamba:params": "8deb1b662411c789",
    "tiny-sala:params": "8a767947314bcc58",
    "tiny-sdar:params": "acc7423ccf4a7c81",
    "tiny-kimi:params": "bbf91b19a6eb7195",
    "tiny-mellum:params": "20131c1b0357fffe",
    "tiny-gigachat:params": "c7fae1c42a16dccc",
    "tiny-laguna:params": "0bdc6201406ce637",
    "tiny-zaya:params": "843b9949a40f3fd0",
}


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


def params_sha(params) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(
            (jax.tree_util.keystr(p), np.asarray(a)) for p, a in leaves):
        h.update(repr((path, leaf.shape, str(leaf.dtype))).encode())
        h.update(leaf.tobytes())
    return h.hexdigest()[:16]


def read_preset(preset: str) -> dict:
    """Every row of one preset, from one engine. From empty caches: what
    jax has traced before decides which private functions a module shares
    and so how it numbers them (`@_where_100` or `@_where_101`)."""
    jax.clear_caches()
    buckets, verify = PRESETS[preset]
    engine = LLMEngine(EngineConfig(model=preset, prefill_buckets=buckets,
                                    **CFG))
    programs = engine._warmup_programs(None, True)
    if verify:
        programs.append(("verify", (32, engine._wave_rb)))
    out = {f"{preset}:{kind}:{key}": _sha(
        engine.program_text(kind, key).encode()) for kind, key in programs}
    out[f"{preset}:params"] = params_sha(engine.compute.params)
    engine.close()
    return out


def read_train_step() -> dict:
    from ray_tpu.models.llama import LlamaModel, get_config
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.train_lib import ShardedTrainer

    jax.clear_caches()
    cfg = get_config("tiny", remat=True, remat_policy="dots")
    mesh = create_mesh(MeshConfig(dp=1, fsdp=1, sp=1, tp=1),
                       devices=jax.devices()[:1])
    trainer = ShardedTrainer(LlamaModel(cfg), mesh)
    batch = {"input_ids": np.zeros((2, 64), np.int32)}
    state = trainer.init(jax.random.PRNGKey(0), batch)
    return {"tiny:train_step": _sha(
        trainer.program_text(state, batch).encode())}


class Rows(dict):
    """row -> sha as this tree gives it; a preset's engine is built on the
    first of its rows that is asked for."""

    def __missing__(self, name: str) -> str:
        self.update(read_train_step() if name == "tiny:train_step"
                    else read_preset(name.split(":")[0]))
        return self[name]


@pytest.fixture(scope="module")
def rows():
    return Rows()


@pytest.mark.parametrize("row", sorted(PINS))
def test_a_program_and_a_parameter_tree_are_what_they_were(rows, row):
    assert rows[row] == PINS[row]


def test_every_warm_up_program_of_every_preset_has_a_row(rows):
    for preset in PRESETS:
        rows[f"{preset}:params"]
    rows["tiny:train_step"]
    assert sorted(rows) == sorted(PINS)


if __name__ == "__main__":  # the rows, as the tree at hand gives them
    import sys

    found = read_train_step()
    for preset in sys.argv[1:] or PRESETS:
        found.update(read_preset(preset))
    for name, sha in found.items():
        print(f'    "{name}": "{sha}",')
