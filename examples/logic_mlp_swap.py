"""The paper's technique inside an LM: FFCL-substituted FFN blocks.

    PYTHONPATH=src python examples/logic_mlp_swap.py

Trains a tiny transformer whose FFNs are *binarized* (NullaNet-compatible,
STE gradients), then converts each FFN's binary hidden map into a
fixed-function combinational logic program (ISF -> espresso -> gates ->
sub-kernel schedule) and serves the model through the logic fabric:
the FFN matmul w_in disappears — inference executes bitwise programs and
never touches those weights (paper §7.1).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.spec import CompileSpec
from repro.data.synthetic import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models import logic_mlp
from repro.models.layers import rms_norm, softmax_xent
from repro.models.transformer import init_params
from repro.models import attention as attn
from repro.optim import adamw_init, adamw_update


def forward(params, cfg, tokens, ffn_fn):
    x = params["embed"].astype(jnp.float32)[tokens]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda a, i=i: a[i], params["blocks"])
        h = rms_norm(x, p["attn_norm"])
        x = x + attn.attention_forward(p, h, cfg, positions=positions)
        h = rms_norm(x, p["mlp_norm"])
        x = x + ffn_fn(i, p, h)
    x = rms_norm(x, params["final_norm"])
    return x @ params["lm_head"].astype(x.dtype)


def main() -> None:
    enable_compile_cache()
    cfg = get_config("qwen3-8b", smoke=True).with_(
        n_layers=2, d_model=48, d_ff=24, n_heads=4, n_kv_heads=2,
        head_dim=12, vocab_size=256)
    params = init_params(cfg, jax.random.PRNGKey(0))
    # replace FFN params with binarized-FFN params
    L, d, f = cfg.n_layers, cfg.d_model, cfg.d_ff
    key = jax.random.PRNGKey(1)
    params["blocks"]["w_in"] = 0.5 * jax.random.normal(key, (L, d, f))
    params["blocks"]["b_in"] = jnp.zeros((L, f))
    params["blocks"]["w_out"] = 0.1 * jax.random.normal(key, (L, f, d))
    for k in ("w_gate", "w_up", "w_down"):
        params["blocks"].pop(k)

    def ste_ffn(i, p, h):
        return logic_mlp.binary_ffn(p, h)

    pipe = TokenPipeline(cfg.vocab_size, global_batch=8, seq_len=32, seed=0)

    def loss_fn(prm, tokens):
        logits = forward(prm, cfg, tokens, ste_ffn)
        return softmax_xent(logits[:, :-1].astype(jnp.float32),
                            tokens[:, 1:])

    opt = adamw_init(params)
    step_fn = jax.jit(lambda p, o, t: (
        lambda l, g: adamw_update(g, o, p, lr=2e-3) + (l,))(
        *jax.value_and_grad(loss_fn)(p, t)))
    for step in range(150):
        tokens = jnp.asarray(pipe.batch(step)["tokens"])
        params, opt, loss = step_fn(params, opt, tokens)
        if step % 50 == 0:
            print(f"step {step}: loss {float(loss):.4f}")

    # --- NullaNet conversion of each FFN ---
    # ISF density drives held-out fidelity (paper §7.1: the samples are a
    # tiny fraction of the 2^48 input space; more calibration -> better
    # don't-care assignments). Capture several batches.
    captured: dict[int, list] = {i: [] for i in range(cfg.n_layers)}

    def capture_ffn(i, p, h):
        captured[i].append(np.asarray((h >= 0).reshape(-1, h.shape[-1])))
        return logic_mlp.binary_ffn(p, h)

    for cb in range(8):
        forward(params, cfg, jnp.asarray(pipe.batch(900 + cb)["tokens"]),
                capture_ffn)
    calib_bits = [(i, np.concatenate(v)) for i, v in captured.items()]
    programs = {}
    for i, bits in calib_bits:
        p = jax.tree.map(lambda a, i=i: a[i], params["blocks"])
        programs[i] = logic_mlp.ffn_to_program(
            {"w_in": p["w_in"], "b_in": p["b_in"]}, bits,
            CompileSpec(n_unit=16), name=f"ffn{i}")
        print(f"layer {i}: FFCL program {programs[i].n_gates} gates, "
              f"{programs[i].n_steps} sub-kernel steps")

    # --- parity: STE forward vs logic-fabric forward ---
    def logic_ffn(i, p, h):
        return logic_mlp.logic_ffn_apply(programs[i], p, h)

    test = jnp.asarray(pipe.batch(1234)["tokens"])
    logits_ste = forward(params, cfg, test, ste_ffn)
    logits_logic = forward(params, cfg, test, logic_ffn)
    loss_ste = float(softmax_xent(logits_ste[:, :-1], test[:, 1:]))
    loss_logic = float(softmax_xent(logits_logic[:, :-1], test[:, 1:]))
    agree = float(jnp.mean(jnp.argmax(logits_ste, -1)
                           == jnp.argmax(logits_logic, -1)))
    print(f"loss: STE {loss_ste:.4f} vs logic-fabric {loss_logic:.4f}")
    print(f"next-token argmax agreement: {agree:.3f} "
          f"(ISF is exact on observed patterns; held-out patterns may "
          f"diverge, paper §7.1)")


if __name__ == "__main__":
    main()
