"""Stable Diffusion UNet + DDIM sampler tests (VERDICT r04 missing #5:
the SD entry needed a real UNet path behind the diffusers attention
processor). No diffusers in this environment, so coverage is: skip/
channel plumbing at real topology ratios, jit + donation, a diffusers-
named state-dict ingest round trip, low-bit transformer linears, and a
deterministic end-to-end DDIM sample."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import sd

# outside tier-1 since ISSUE 56 (ROADMAP D13 (1)): 8 cases, 179 test-seconds,
# and `models/sd.py` stands outside every benchmark cell's program (D7)
pytestmark = pytest.mark.slow

CFG = sd.SDConfig(
    in_channels=4, out_channels=4,
    block_out_channels=(32, 64, 96, 96), layers_per_block=2,
    cross_attention_dim=24, attention_head_dim=4, norm_num_groups=8,
)



def _fake_unet_store(cfg, rng):
    """diffusers-named UNet state dict of the right shapes."""
    store = {}

    def fake(name, shape):
        store[name] = rng.standard_normal(shape).astype(np.float32) * 0.02

    te = cfg.time_embed_dim
    xd = cfg.cross_attention_dim
    chans = cfg.block_out_channels

    def add_resnet(pre, cin, cout):
        fake(f"{pre}.norm1.weight", (cin,)); fake(f"{pre}.norm1.bias", (cin,))
        fake(f"{pre}.conv1.weight", (cout, cin, 3, 3))
        fake(f"{pre}.conv1.bias", (cout,))
        fake(f"{pre}.time_emb_proj.weight", (cout, te))
        fake(f"{pre}.time_emb_proj.bias", (cout,))
        fake(f"{pre}.norm2.weight", (cout,)); fake(f"{pre}.norm2.bias", (cout,))
        fake(f"{pre}.conv2.weight", (cout, cout, 3, 3))
        fake(f"{pre}.conv2.bias", (cout,))
        if cin != cout:
            fake(f"{pre}.conv_shortcut.weight", (cout, cin, 1, 1))
            fake(f"{pre}.conv_shortcut.bias", (cout,))

    def add_attn(pre, c):
        fake(f"{pre}.norm.weight", (c,)); fake(f"{pre}.norm.bias", (c,))
        fake(f"{pre}.proj_in.weight", (c, c, 1, 1))
        fake(f"{pre}.proj_in.bias", (c,))
        b = f"{pre}.transformer_blocks.0"
        for ln in ("norm1", "norm2", "norm3"):
            fake(f"{b}.{ln}.weight", (c,)); fake(f"{b}.{ln}.bias", (c,))
        for a, kdim in (("attn1", c), ("attn2", xd)):
            fake(f"{b}.{a}.to_q.weight", (c, c))
            fake(f"{b}.{a}.to_k.weight", (c, kdim))
            fake(f"{b}.{a}.to_v.weight", (c, kdim))
            fake(f"{b}.{a}.to_out.0.weight", (c, c))
            fake(f"{b}.{a}.to_out.0.bias", (c,))
        fake(f"{b}.ff.net.0.proj.weight", (8 * c, c))
        fake(f"{b}.ff.net.0.proj.bias", (8 * c,))
        fake(f"{b}.ff.net.2.weight", (c, 4 * c))
        fake(f"{b}.ff.net.2.bias", (c,))
        fake(f"{pre}.proj_out.weight", (c, c, 1, 1))
        fake(f"{pre}.proj_out.bias", (c,))

    fake("conv_in.weight", (chans[0], cfg.in_channels, 3, 3))
    fake("conv_in.bias", (chans[0],))
    fake("time_embedding.linear_1.weight", (te, chans[0]))
    fake("time_embedding.linear_1.bias", (te,))
    fake("time_embedding.linear_2.weight", (te, te))
    fake("time_embedding.linear_2.bias", (te,))
    fake("conv_norm_out.weight", (chans[0],))
    fake("conv_norm_out.bias", (chans[0],))
    fake("conv_out.weight", (cfg.out_channels, chans[0], 3, 3))
    fake("conv_out.bias", (cfg.out_channels,))
    for bi, res in enumerate(sd._down_channels(cfg)):
        c = chans[bi]
        for li, (a, b) in enumerate(res):
            add_resnet(f"down_blocks.{bi}.resnets.{li}", a, b)
        if bi < len(chans) - 1:
            for li in range(len(res)):
                add_attn(f"down_blocks.{bi}.attentions.{li}", c)
            fake(f"down_blocks.{bi}.downsamplers.0.conv.weight", (c, c, 3, 3))
            fake(f"down_blocks.{bi}.downsamplers.0.conv.bias", (c,))
    cm = chans[-1]
    add_resnet("mid_block.resnets.0", cm, cm)
    add_resnet("mid_block.resnets.1", cm, cm)
    add_attn("mid_block.attentions.0", cm)
    for bi, res in enumerate(sd._up_channels(cfg)):
        c = chans[::-1][bi]
        for li, (a, b) in enumerate(res):
            add_resnet(f"up_blocks.{bi}.resnets.{li}", a, b)
        if bi > 0:
            for li in range(len(res)):
                add_attn(f"up_blocks.{bi}.attentions.{li}", c)
        if bi < len(chans) - 1:
            fake(f"up_blocks.{bi}.upsamplers.0.conv.weight", (c, c, 3, 3))
            fake(f"up_blocks.{bi}.upsamplers.0.conv.bias", (c,))
    return store


def _fake_vae_store(vcfg, rng):
    """diffusers-named AutoencoderKL (decoder) state dict."""
    store = {}

    def fake(name, shape):
        store[name] = rng.standard_normal(shape).astype(np.float32) * 0.02

    chans = vcfg.block_out_channels
    cm, c0 = chans[-1], chans[0]
    lc = vcfg.latent_channels

    def add_resnet(pre, cin, cout):
        fake(f"{pre}.norm1.weight", (cin,)); fake(f"{pre}.norm1.bias", (cin,))
        fake(f"{pre}.conv1.weight", (cout, cin, 3, 3))
        fake(f"{pre}.conv1.bias", (cout,))
        fake(f"{pre}.norm2.weight", (cout,)); fake(f"{pre}.norm2.bias", (cout,))
        fake(f"{pre}.conv2.weight", (cout, cout, 3, 3))
        fake(f"{pre}.conv2.bias", (cout,))
        if cin != cout:
            fake(f"{pre}.conv_shortcut.weight", (cout, cin, 1, 1))
            fake(f"{pre}.conv_shortcut.bias", (cout,))

    fake("post_quant_conv.weight", (lc, lc, 1, 1))
    fake("post_quant_conv.bias", (lc,))
    fake("decoder.conv_in.weight", (cm, lc, 3, 3))
    fake("decoder.conv_in.bias", (cm,))
    add_resnet("decoder.mid_block.resnets.0", cm, cm)
    add_resnet("decoder.mid_block.resnets.1", cm, cm)
    fake("decoder.mid_block.attentions.0.group_norm.weight", (cm,))
    fake("decoder.mid_block.attentions.0.group_norm.bias", (cm,))
    for n in ("to_q", "to_k", "to_v"):
        fake(f"decoder.mid_block.attentions.0.{n}.weight", (cm, cm))
        fake(f"decoder.mid_block.attentions.0.{n}.bias", (cm,))
    fake("decoder.mid_block.attentions.0.to_out.0.weight", (cm, cm))
    fake("decoder.mid_block.attentions.0.to_out.0.bias", (cm,))
    rev = list(chans)[::-1]
    for bi, c in enumerate(rev):
        prev = rev[bi - 1] if bi else rev[0]
        for li in range(vcfg.layers_per_block + 1):
            add_resnet(f"decoder.up_blocks.{bi}.resnets.{li}",
                       prev if li == 0 else c, c)
        if bi < len(rev) - 1:
            fake(f"decoder.up_blocks.{bi}.upsamplers.0.conv.weight",
                 (c, c, 3, 3))
            fake(f"decoder.up_blocks.{bi}.upsamplers.0.conv.bias", (c,))
    fake("decoder.conv_norm_out.weight", (c0,))
    fake("decoder.conv_norm_out.bias", (c0,))
    fake("decoder.conv_out.weight", (vcfg.out_channels, c0, 3, 3))
    fake("decoder.conv_out.bias", (vcfg.out_channels,))
    return store


@pytest.fixture(scope="module")
def params():
    return sd.init_params(CFG, jax.random.PRNGKey(0))


def test_unet_forward_shapes_and_jit(params):
    """Latent through the full down/mid/up path (3 downsamples on a
    32x32 latent) returns the eps prediction at input resolution."""
    B, H = 2, 32
    lat = jax.random.normal(jax.random.PRNGKey(1), (B, H, H, 4))
    ctx = jax.random.normal(jax.random.PRNGKey(2), (B, 7, 24))
    t = jnp.asarray([3, 500], jnp.int32)
    fwd = jax.jit(lambda l, tt, c: sd.unet_forward(CFG, params, l, tt, c))
    eps = fwd(lat, t, ctx)
    assert eps.shape == (B, H, H, 4)
    assert np.isfinite(np.asarray(eps)).all()
    # timestep conditioning is live: different t, different eps
    eps2 = fwd(lat, jnp.asarray([900, 3], jnp.int32), ctx)
    assert float(jnp.max(jnp.abs(eps - eps2))) > 1e-4
    # text conditioning is live
    eps3 = fwd(lat, t, ctx * 0.5)
    assert float(jnp.max(jnp.abs(eps - eps3))) > 1e-4


def test_state_dict_ingest_matches_init_topology(params):
    """A diffusers-named state dict of the right shapes ingests into a
    tree the forward accepts, proving the name/transpose plumbing."""
    rng = np.random.default_rng(0)
    store = {}

    def fake(name, shape):
        store[name] = rng.standard_normal(shape).astype(np.float32) * 0.02
        return store[name]

    te = CFG.time_embed_dim
    xd = CFG.cross_attention_dim
    chans = CFG.block_out_channels

    def add_resnet(pre, cin, cout):
        fake(f"{pre}.norm1.weight", (cin,)); fake(f"{pre}.norm1.bias", (cin,))
        fake(f"{pre}.conv1.weight", (cout, cin, 3, 3))
        fake(f"{pre}.conv1.bias", (cout,))
        fake(f"{pre}.time_emb_proj.weight", (cout, te))
        fake(f"{pre}.time_emb_proj.bias", (cout,))
        fake(f"{pre}.norm2.weight", (cout,)); fake(f"{pre}.norm2.bias", (cout,))
        fake(f"{pre}.conv2.weight", (cout, cout, 3, 3))
        fake(f"{pre}.conv2.bias", (cout,))
        if cin != cout:
            fake(f"{pre}.conv_shortcut.weight", (cout, cin, 1, 1))
            fake(f"{pre}.conv_shortcut.bias", (cout,))

    def add_attn(pre, c):
        fake(f"{pre}.norm.weight", (c,)); fake(f"{pre}.norm.bias", (c,))
        fake(f"{pre}.proj_in.weight", (c, c, 1, 1))
        fake(f"{pre}.proj_in.bias", (c,))
        b = f"{pre}.transformer_blocks.0"
        for ln in ("norm1", "norm2", "norm3"):
            fake(f"{b}.{ln}.weight", (c,)); fake(f"{b}.{ln}.bias", (c,))
        for a, kdim in (("attn1", c), ("attn2", xd)):
            fake(f"{b}.{a}.to_q.weight", (c, c))
            fake(f"{b}.{a}.to_k.weight", (c, kdim))
            fake(f"{b}.{a}.to_v.weight", (c, kdim))
            fake(f"{b}.{a}.to_out.0.weight", (c, c))
            fake(f"{b}.{a}.to_out.0.bias", (c,))
        fake(f"{b}.ff.net.0.proj.weight", (8 * c, c))
        fake(f"{b}.ff.net.0.proj.bias", (8 * c,))
        fake(f"{b}.ff.net.2.weight", (c, 4 * c))
        fake(f"{b}.ff.net.2.bias", (c,))
        fake(f"{pre}.proj_out.weight", (c, c, 1, 1))
        fake(f"{pre}.proj_out.bias", (c,))

    fake("conv_in.weight", (chans[0], 4, 3, 3))
    fake("conv_in.bias", (chans[0],))
    fake("time_embedding.linear_1.weight", (te, chans[0]))
    fake("time_embedding.linear_1.bias", (te,))
    fake("time_embedding.linear_2.weight", (te, te))
    fake("time_embedding.linear_2.bias", (te,))
    fake("conv_norm_out.weight", (chans[0],))
    fake("conv_norm_out.bias", (chans[0],))
    fake("conv_out.weight", (4, chans[0], 3, 3))
    fake("conv_out.bias", (4,))
    for bi, res in enumerate(sd._down_channels(CFG)):
        c = chans[bi]
        for li, (a, b) in enumerate(res):
            add_resnet(f"down_blocks.{bi}.resnets.{li}", a, b)
        if bi < len(chans) - 1:
            for li in range(len(res)):
                add_attn(f"down_blocks.{bi}.attentions.{li}", c)
            fake(f"down_blocks.{bi}.downsamplers.0.conv.weight", (c, c, 3, 3))
            fake(f"down_blocks.{bi}.downsamplers.0.conv.bias", (c,))
    cm = chans[-1]
    add_resnet("mid_block.resnets.0", cm, cm)
    add_resnet("mid_block.resnets.1", cm, cm)
    add_attn("mid_block.attentions.0", cm)
    for bi, res in enumerate(sd._up_channels(CFG)):
        c = chans[::-1][bi]
        for li, (a, b) in enumerate(res):
            add_resnet(f"up_blocks.{bi}.resnets.{li}", a, b)
        if bi > 0:
            for li in range(len(res)):
                add_attn(f"up_blocks.{bi}.attentions.{li}", c)
        if bi < len(chans) - 1:
            fake(f"up_blocks.{bi}.upsamplers.0.conv.weight", (c, c, 3, 3))
            fake(f"up_blocks.{bi}.upsamplers.0.conv.bias", (c,))

    ingested = sd.params_from_state_dict(CFG, lambda n: store[n])
    lat = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 16, 4))
    ctx = jax.random.normal(jax.random.PRNGKey(4), (1, 5, 24))
    eps = sd.unet_forward(CFG, ingested, lat, jnp.asarray([10]), ctx)
    assert eps.shape == (1, 16, 16, 4)
    assert np.isfinite(np.asarray(eps)).all()


def test_quantized_linears_stay_close(params):
    cfg = sd.SDConfig(
        block_out_channels=(64, 64), layers_per_block=1,
        cross_attention_dim=64, attention_head_dim=4, norm_num_groups=8,
    )
    p = sd.init_params(cfg, jax.random.PRNGKey(5))
    qp = sd.quantize_params(p, "sym_int8")
    from bigdl_tpu.quant import QTensor

    leaves = jax.tree.leaves(qp, is_leaf=lambda x: isinstance(x, QTensor))
    assert any(isinstance(x, QTensor) for x in leaves)
    lat = jax.random.normal(jax.random.PRNGKey(6), (1, 16, 16, 4))
    ctx = jax.random.normal(jax.random.PRNGKey(7), (1, 4, 64))
    dense = sd.unet_forward(cfg, p, lat, jnp.asarray([100]), ctx)
    low = sd.unet_forward(cfg, qp, lat, jnp.asarray([100]), ctx)
    err = float(jnp.mean(jnp.abs(dense - low)) / (jnp.mean(jnp.abs(dense)) + 1e-9))
    assert err < 0.15, err


def test_ddim_sample_deterministic(params):
    lat = jax.random.normal(jax.random.PRNGKey(8), (1, 16, 16, 4))
    txt = jax.random.normal(jax.random.PRNGKey(9), (1, 5, 24))
    unc = jnp.zeros((1, 5, 24))
    out1 = sd.ddim_sample(CFG, params, txt, unc, lat, num_steps=3,
                          guidance_scale=5.0)
    out2 = sd.ddim_sample(CFG, params, txt, unc, lat, num_steps=3,
                          guidance_scale=5.0)
    assert out1.shape == lat.shape
    assert np.isfinite(np.asarray(out1)).all()
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # guidance is live
    out3 = sd.ddim_sample(CFG, params, txt, unc, lat, num_steps=3,
                          guidance_scale=1.0)
    assert float(jnp.max(jnp.abs(out1 - out3))) > 1e-4


def test_vae_decoder_shapes_and_ingest():
    """VAE decoder: latents upsample 2^(n_blocks-1)x to pixels; a
    diffusers-named AutoencoderKL state dict ingests and runs."""
    vcfg = sd.VAEConfig(block_out_channels=(16, 32, 32), layers_per_block=1,
                        norm_num_groups=8)
    p = sd.init_vae_params(vcfg, jax.random.PRNGKey(10))
    lat = jax.random.normal(jax.random.PRNGKey(11), (1, 8, 8, 4))
    img = jax.jit(lambda l: sd.vae_decode(vcfg, p, l))(lat)
    assert img.shape == (1, 32, 32, 3)  # two upsamples
    assert np.isfinite(np.asarray(img)).all()

    rng = np.random.default_rng(1)
    store = {}

    def fake(name, shape):
        store[name] = rng.standard_normal(shape).astype(np.float32) * 0.02

    chans = vcfg.block_out_channels
    cm, c0 = chans[-1], chans[0]

    def add_resnet(pre, cin, cout):
        fake(f"{pre}.norm1.weight", (cin,)); fake(f"{pre}.norm1.bias", (cin,))
        fake(f"{pre}.conv1.weight", (cout, cin, 3, 3))
        fake(f"{pre}.conv1.bias", (cout,))
        fake(f"{pre}.norm2.weight", (cout,)); fake(f"{pre}.norm2.bias", (cout,))
        fake(f"{pre}.conv2.weight", (cout, cout, 3, 3))
        fake(f"{pre}.conv2.bias", (cout,))
        if cin != cout:
            fake(f"{pre}.conv_shortcut.weight", (cout, cin, 1, 1))
            fake(f"{pre}.conv_shortcut.bias", (cout,))

    fake("post_quant_conv.weight", (4, 4, 1, 1))
    fake("post_quant_conv.bias", (4,))
    fake("decoder.conv_in.weight", (cm, 4, 3, 3))
    fake("decoder.conv_in.bias", (cm,))
    add_resnet("decoder.mid_block.resnets.0", cm, cm)
    add_resnet("decoder.mid_block.resnets.1", cm, cm)
    fake("decoder.mid_block.attentions.0.group_norm.weight", (cm,))
    fake("decoder.mid_block.attentions.0.group_norm.bias", (cm,))
    for n in ("to_q", "to_k", "to_v"):
        fake(f"decoder.mid_block.attentions.0.{n}.weight", (cm, cm))
        fake(f"decoder.mid_block.attentions.0.{n}.bias", (cm,))
    fake("decoder.mid_block.attentions.0.to_out.0.weight", (cm, cm))
    fake("decoder.mid_block.attentions.0.to_out.0.bias", (cm,))
    rev = list(chans)[::-1]
    for bi, c in enumerate(rev):
        prev = rev[bi - 1] if bi else rev[0]
        for li in range(vcfg.layers_per_block + 1):
            add_resnet(f"decoder.up_blocks.{bi}.resnets.{li}",
                       prev if li == 0 else c, c)
        if bi < len(rev) - 1:
            fake(f"decoder.up_blocks.{bi}.upsamplers.0.conv.weight",
                 (c, c, 3, 3))
            fake(f"decoder.up_blocks.{bi}.upsamplers.0.conv.bias", (c,))
    fake("decoder.conv_norm_out.weight", (c0,))
    fake("decoder.conv_norm_out.bias", (c0,))
    fake("decoder.conv_out.weight", (3, c0, 3, 3))
    fake("decoder.conv_out.bias", (3,))

    ingested = sd.vae_params_from_state_dict(vcfg, lambda n: store[n])
    img2 = sd.vae_decode(vcfg, ingested, lat)
    assert img2.shape == (1, 32, 32, 3)
    assert np.isfinite(np.asarray(img2)).all()


def test_clip_text_encoder_matches_hf():
    """SD's conditioning model against transformers' CLIPTextModel
    (fp32 CPU eager): last_hidden_state equivalence, both activations."""
    torch = pytest.importorskip("torch")
    from transformers import CLIPTextConfig, CLIPTextModel

    from bigdl_tpu.models import clip_text

    for act in ("quick_gelu", "gelu"):
        hf_cfg = CLIPTextConfig(
            vocab_size=99, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=16, hidden_act=act,
        )
        hf_cfg._attn_implementation = "eager"
        torch.manual_seed(0)
        m = CLIPTextModel(hf_cfg).eval().to(torch.float32)

        ids = np.asarray([[3, 1, 4, 1, 5, 9, 2, 6],
                          [2, 7, 1, 8, 2, 8, 1, 8]], np.int64)
        with torch.no_grad():
            want = m(torch.from_numpy(ids)).last_hidden_state.numpy()

        cfg = clip_text.ClipTextConfig.from_hf(hf_cfg.to_dict())
        sd_ = m.state_dict()
        params = clip_text.params_from_state_dict(
            cfg, lambda n: sd_[n].numpy()
        )
        ours = clip_text.forward(cfg, params, jnp.asarray(ids, jnp.int32))
        np.testing.assert_allclose(np.asarray(ours), want,
                                   rtol=2e-3, atol=2e-3)


def test_text_to_image_end_to_end():
    """The full pipeline (CLIP encode -> DDIM -> VAE decode) runs as one
    program chain and returns [0,1] images at the requested size."""
    from bigdl_tpu.models import clip_text

    ccfg = clip_text.ClipTextConfig(
        vocab_size=64, hidden_size=24, intermediate_size=48,
        num_hidden_layers=1, num_attention_heads=4,
        max_position_embeddings=8,
    )
    ucfg = sd.SDConfig(
        block_out_channels=(16, 32), layers_per_block=1,
        cross_attention_dim=24, attention_head_dim=4, norm_num_groups=8,
    )
    vcfg = sd.VAEConfig(block_out_channels=(8, 16), layers_per_block=1,
                        norm_num_groups=4)
    img = sd.text_to_image(
        ucfg, sd.init_params(ucfg, jax.random.PRNGKey(0)),
        ccfg, clip_text.init_params(ccfg, jax.random.PRNGKey(1)),
        vcfg, sd.init_vae_params(vcfg, jax.random.PRNGKey(2)),
        prompt_ids=jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32),
        uncond_ids=jnp.zeros((1, 8), jnp.int32),
        key=jax.random.PRNGKey(3),
        height=32, width=32, num_steps=2, guidance_scale=4.0,
    )
    # latent 4x4 (H/8) -> VAE upsamples 2x -> pixels... the tiny VAE has
    # one upsample, so pixels land at H/4: assert against the real ratio
    assert img.shape == (1, 8, 8, 3)
    a = np.asarray(img)
    assert np.isfinite(a).all() and a.min() >= 0.0 and a.max() <= 1.0


def test_load_diffusers_pipeline_and_cli_txt2img(tmp_path):
    """A fake diffusers checkpoint dir (unet/ + vae/ + text_encoder/
    safetensors + configs) loads into SDPipeline, generates, and the
    txt2img CLI writes a valid PNG."""
    torch = pytest.importorskip("torch")
    import json
    import subprocess
    import sys

    from safetensors.numpy import save_file
    from transformers import CLIPTextConfig, CLIPTextModel

    rng = np.random.default_rng(3)
    ucfg = sd.SDConfig(
        block_out_channels=(16, 32), layers_per_block=1,
        cross_attention_dim=24, attention_head_dim=4, norm_num_groups=8,
    )
    vcfg = sd.VAEConfig(block_out_channels=(8, 16), layers_per_block=1,
                        norm_num_groups=4)
    hf_clip = CLIPTextConfig(
        vocab_size=64, hidden_size=24, intermediate_size=48,
        num_hidden_layers=1, num_attention_heads=4,
        max_position_embeddings=8,
    )
    torch.manual_seed(0)
    clip_sd = {k: v.detach().float().numpy()
               for k, v in CLIPTextModel(hf_clip).state_dict().items()}

    for sub, cfg_json, store in (
        ("unet", {"in_channels": 4, "out_channels": 4,
                  "block_out_channels": [16, 32], "layers_per_block": 1,
                  "cross_attention_dim": 24, "attention_head_dim": 4,
                  "norm_num_groups": 8}, _fake_unet_store(ucfg, rng)),
        ("vae", {"latent_channels": 4, "out_channels": 3,
                 "block_out_channels": [8, 16], "layers_per_block": 1,
                 "norm_num_groups": 4}, _fake_vae_store(vcfg, rng)),
        ("text_encoder", hf_clip.to_dict(), clip_sd),
    ):
        d = tmp_path / sub
        d.mkdir()
        (d / "config.json").write_text(json.dumps(cfg_json))
        save_file(store, str(d / "diffusion_pytorch_model.safetensors"))

    pipe = sd.load_diffusers_pipeline(str(tmp_path))
    assert pipe.tokenizer is None  # no tokenizer dir: ids-only mode
    imgs = pipe([3, 1, 4, 1, 5], height=32, width=32, num_steps=2,
                guidance_scale=3.0)
    assert imgs.dtype == np.uint8 and imgs.shape[0] == 1

    out = tmp_path / "img.png"
    import pathlib
    repo = pathlib.Path(__file__).resolve().parent.parent
    r = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", "txt2img", str(tmp_path),
         "-p", "3 1 4", "-o", str(out), "--size", "32", "--steps", "2"],
        capture_output=True, text=True, timeout=500,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": str(repo), "HOME": "/tmp"},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    data = out.read_bytes()
    assert data[:8] == b"\x89PNG\r\n\x1a\n"
    assert b"IHDR" in data[:33] and b"IEND" in data[-16:]
