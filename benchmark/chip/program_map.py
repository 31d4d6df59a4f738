"""Where the reference's weights sit in the program's scope.

The reference (reference/transformer2017.py) names one matrix per
projection; `models/transformer.py` fuses q, k, v (and the cross
attention's k, v) into one wider matrix. A program leaf is therefore
one or several reference leaves side by side, and a norm over a program
leaf is the root of the sum of its parts' squares."""
import math


def program_leaves(n_layers):
    """program variable name -> reference names, concatenated along
    the last axis in that order."""
    out = {"src_word_emb": ["src_emb"], "tgt_word_emb": ["tgt_emb"],
           "logits.w": ["out_proj"]}

    def norm(prog, ref):
        out[f"{prog}_ln.w"] = [f"{ref}.g"]
        out[f"{prog}_ln.b"] = [f"{ref}.b"]

    def ffn(prog, ref):
        for a, b in (("fc1.w", "w1"), ("fc1.b", "b1"),
                     ("fc2.w", "w2"), ("fc2.b", "b2")):
            out[f"{prog}_{a}"] = [f"{ref}.ffn.{b}"]

    for i in range(n_layers):
        e, d = f"enc{i}", f"dec{i}"
        out[f"{e}_self_qkv.w"] = [f"{e}.self.w{x}" for x in "qkv"]
        out[f"{e}_self_out.w"] = [f"{e}.self.wo"]
        norm(f"{e}_a", f"{e}.ln1")
        ffn(e, e)
        norm(f"{e}_b", f"{e}.ln2")
        out[f"{d}_self_qkv.w"] = [f"{d}.self.w{x}" for x in "qkv"]
        out[f"{d}_self_out.w"] = [f"{d}.self.wo"]
        norm(f"{d}_a", f"{d}.ln1")
        out[f"{d}_cross_q.w"] = [f"{d}.cross.wq"]
        out[f"{d}_cross_kv.w"] = [f"{d}.cross.wk", f"{d}.cross.wv"]
        out[f"{d}_cross_out.w"] = [f"{d}.cross.wo"]
        norm(f"{d}_b", f"{d}.ln2")
        ffn(d, d)
        norm(f"{d}_c", f"{d}.ln3")
    return out


def to_program(ref_params, n_layers):
    """The program's arrays from the reference's."""
    import jax.numpy as jnp

    return {name: ref_params[parts[0]] if len(parts) == 1
            else jnp.concatenate([ref_params[p] for p in parts], axis=-1)
            for name, parts in program_leaves(n_layers).items()}


def group_norms(ref_norms, n_layers):
    """Per-program-leaf norms from per-reference-leaf norms."""
    return {name: math.sqrt(sum(ref_norms[p] ** 2 for p in parts))
            for name, parts in program_leaves(n_layers).items()}
