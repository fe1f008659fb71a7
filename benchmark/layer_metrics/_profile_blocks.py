"""Shared by the readers of the capture's second summary, by kind of model
block. `ProfileWindow` (commefficient_tpu/obs/profiler.py) reduces the capture
it wrote a second time, by the `jax.named_scope`s a model puts around its
blocks (`gdn`, `gated_attn`, `moe_route`, `moe_experts`, `moe_shared`,
`lm_head`; backward operations carry them as transpose(jvp(<name>))), and
publishes device ms per traced round as gauges `profile_block_device_ms_<name>`.
A program that has no such reduction, as the parent of PR 27, or a model that
names no block, reads nothing."""


def block_ms(*blocks: str):
    from commefficient_tpu.obs import registry as obreg

    reg = obreg.default()
    if not reg.gauge("profile_block_traced_rounds").value:
        return None
    return sum(reg.gauge(f"profile_block_device_ms_{b}").value for b in blocks)
