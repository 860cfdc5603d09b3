"""The mixed window program as it stood before the value plane left the
scan's carry (PR 35's ``DeviceKVTable._build_mixed``, unchanged but for
being a plain function of the table): every wave fetches its GET row
from the plane by a one-hot multiply-reduce over all P slots and writes
its SET row by a select over all P slots. Slow on a chip and plain to
read, so it is the reference ``tests/test_device_kv.py`` holds the
shipped program to, bit for bit. Not imported by the package."""

from rabia_tpu.core.types import V0, V1


def build_per_wave_mixed(table, Ku4: int, VWu4: int):
    """The jitted mixed window of ``table`` with the value plane in the
    scan's carry: same arguments, same four outputs as the program
    ``DeviceKVTable._build_mixed`` builds (``Gp`` is ``gidx``'s length)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    kernel = table.kernel
    S, Pc = table.S, table.P
    K4, VW4 = table.K4, table.VW4
    n = table.n_shards
    I8, I32 = jnp.int8, jnp.int32
    col = jnp.arange(S) < n

    def mixed(state, alive, base, depth, kind_w, gidx, ops, *, W,
              max_phases):
        with jax.named_scope("consensus"):
            wave = jnp.arange(W, dtype=I32)[:, None] < depth
            present = wave & col[None, :]
            votes = jnp.where(
                present[:, :, None], I8(V1), I8(V0)
            ) * jnp.ones((1, 1, kernel.R), I8)
            decided = kernel.slot_window(
                votes, alive, base, n_slots=W, max_phases=max_phases
            )
            all_v1 = jnp.all(jnp.where(present, decided == V1, True))

        def wave_step(carry, inp):
            ok_w, kind_t, klen_t, vlen_t, kwin_t, vwin_t = inp
            used, keyw, klen, ver, valw, vlen, sver = carry
            klen_t = klen_t.astype(jnp.int32)
            vlen_t = vlen_t.astype(jnp.int32)
            kind_t = kind_t.astype(jnp.int32)
            with jax.named_scope("key_match"):
                eq = (
                    used
                    & (klen == klen_t[:, None])
                    & (keyw == kwin_t[:, None, :]).all(-1)
                )  # [S, P]
                found = eq.any(1)
            # reads (GET/DEL/EXISTS found bits) are against the
            # wave-entry state, before this wave's applies touch the
            # table; gver/gval carry data for GET ops only (a DEL's
            # response is its found bit, an EXISTS's is a boolean)
            with jax.named_scope("get_gather"):
                rsel = (kind_t >= 2) & (klen_t > 0)
                gsel = found & rsel
                oh_get = eq & (found & (kind_t == 2))[:, None]
                gver = (ver * oh_get).sum(1)
                gvlen = (vlen * oh_get).sum(1)
                gval = (valw * oh_get[:, :, None]).sum(1)
            with jax.named_scope("apply_set"):
                # DEL applies: clear the matched slot (the table is
                # compare-all associative — no probe chains to
                # repair, unlike the host twin's open addressing) and
                # bump the shard version exactly like the host
                # store's delete() does on a successful delete
                del_hit = ok_w & (kind_t == 3) & found
                used = used & ~(eq & del_hit[:, None])
                sver = sver + del_hit
                # SET applies: same one-hot word-select update as the
                # pure-SET program, gated on this op BEING a SET
                is_set = ok_w & (kind_t == 1)
                slot = jnp.where(
                    found, jnp.argmax(eq, 1), jnp.argmax(~used, 1)
                )
                full = used.all(1)
                apply = is_set & (found | ~full)
                overflow = jnp.any(is_set & ~found & full)
                onehot = (
                    jnp.arange(Pc)[None, :] == slot[:, None]
                ) & apply[:, None]
                oh3 = onehot[:, :, None]
                used = used | onehot
                keyw = jnp.where(oh3, kwin_t[:, None, :], keyw)
                klen = jnp.where(onehot, klen_t[:, None], klen)
                new_ver = sver + 1
                ver = jnp.where(onehot, new_ver[:, None], ver)
                valw = jnp.where(oh3, vwin_t[:, None, :], valw)
                vlen = jnp.where(onehot, vlen_t[:, None], vlen)
                sver = jnp.where(apply, new_ver, sver)
            return (used, keyw, klen, ver, valw, vlen, sver), (
                overflow,
                gsel,
                gver,
                gvlen,
                gval,
            )

        kwin_full = jnp.pad(ops.kwin, ((0, 0), (0, 0), (0, K4 - Ku4)))
        vwin_full = jnp.pad(ops.vwin, ((0, 0), (0, 0), (0, VW4 - VWu4)))
        xs = (present, kind_w, ops.klen, ops.vlen, kwin_full, vwin_full)
        new_state, (over_w, gfound, gver, gvlen, gval) = lax.scan(
            wave_step, state, xs
        )
        with jax.named_scope("flags"):
            flags = jnp.stack(
                [
                    all_v1.astype(I32),
                    jnp.any(over_w).astype(I32),
                    jnp.any(
                        new_state[6] >= jnp.int32(2**31 - 2)
                    ).astype(I32),
                ]
            )
        # device-side gather of the GET-bearing waves + two-plane
        # meta pack: [0]=version, [1]=(vlen<<1)|found
        with jax.named_scope("get_gather"):
            gfound_g = jnp.take(gfound, gidx, axis=0).astype(I32)
            gver_g = jnp.take(gver, gidx, axis=0)
            gvlen_g = jnp.take(gvlen, gidx, axis=0)
            gval_g = jnp.take(gval, gidx, axis=0)
            meta = jnp.stack([gver_g, (gvlen_g << 1) | gfound_g])
        return new_state, flags, meta, gval_g

    return jax.jit(mixed, static_argnames=("W", "max_phases"))
