"""What the drivers share: the program's model built from a configuration
file, and the weights the harness makes handed to the program's layout."""
from __future__ import annotations

import dataclasses

import jax

# configuration key -> the program's ArchConfig field
ARCH_FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "torch_dtype": "param_dtype",
}


def program_model(cfg: dict):
    """The program's model for this configuration file: its architecture
    ``program_arch`` with every size the file states."""
    from repro.configs import get_arch
    from repro.models import build_model
    arch = get_arch(cfg["program_arch"])
    fields = {f: cfg[k] for k, f in ARCH_FIELDS.items()}
    fields["compute_dtype"] = cfg["torch_dtype"]
    arch = dataclasses.replace(arch, name=f"{arch.name}-bench", **fields)
    if arch.tie_embeddings != cfg["tie_word_embeddings"]:
        raise ValueError("tie_word_embeddings differs from the program's "
                         f"{cfg['program_arch']}")
    return build_model(arch)


def to_program(w: dict) -> dict:
    """Reference-named weights -> the program's parameter tree (the same
    arrays, nested as ``repro.models.lm.init`` nests them)."""
    lin = lambda a: {"w": a}  # noqa: E731
    return {
        "embed": {"table": w["embed"]},
        "stack": {"blocks": {"l0": {
            "ln1": {"scale": w["ln1"]},
            "mixer": {"wq": lin(w["wq"]), "wk": lin(w["wk"]),
                      "wv": lin(w["wv"]), "wo": lin(w["wo"])},
            "ln2": {"scale": w["ln2"]},
            "ffn": {"wg": lin(w["wg"]), "wi": lin(w["wi"]),
                    "wo": lin(w["w2"])}}}},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": {"w": w["lm_head"]},
    }


def from_program(p: dict) -> dict:
    """The inverse of :func:`to_program`."""
    l0 = p["stack"]["blocks"]["l0"]
    mx, ff = l0["mixer"], l0["ffn"]
    return {"embed": p["embed"]["table"], "ln1": l0["ln1"]["scale"],
            "wq": mx["wq"]["w"], "wk": mx["wk"]["w"], "wv": mx["wv"]["w"],
            "wo": mx["wo"]["w"], "ln2": l0["ln2"]["scale"],
            "wg": ff["wg"]["w"], "wi": ff["wi"]["w"], "w2": ff["wo"]["w"],
            "final_norm": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]["w"]}


def check_layout(model, params) -> None:
    """Raise unless ``params`` has exactly the structure, shapes and types
    of the program's own initialisation."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the harness's weights do not match the program's "
                         f"parameter layout:\n{want}\n{got}")
