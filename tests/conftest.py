"""Test-session configuration.

x64 is enabled so the paper's exact algebraic invariants (telescoping,
unbiasedness, delta-replacement) can be asserted to near machine precision;
model code is dtype-explicit so the zoo still exercises its configured
float32/bfloat16 paths.

NOTE: XLA_FLAGS device-count forcing deliberately does NOT happen here —
smoke tests and benches must see the real single CPU device; only
``repro/launch/dryrun.py`` forces 512 placeholder devices (see that file).
Mesh-semantics tests spawn a subprocess with the flag instead.

The persistent compilation cache is off for the session: a launcher that
enables it would otherwise write every later test's compiles to disk.
Only the cache's own test turns it back on.
"""
import jax

jax.config.update("jax_enable_x64", True)
jax.config.update("jax_enable_compilation_cache", False)
