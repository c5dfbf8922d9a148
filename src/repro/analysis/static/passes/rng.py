"""Pass 4: RNG hygiene (the PR-5 bug class).

A small abstract interpreter over the jaxpr's PRNG-key dataflow.  Every
key gets a stable identity derived from how it was made:

  * ``random_seed`` / untracked ``random_wrap``      -> fresh root
  * ``random_split(k)``                              -> ``k.split`` array;
    extracting subkey *i* (the unwrap -> slice -> squeeze -> wrap chain
    jax emits for ``keys[i]``) yields ``k.split[i]``
  * ``random_fold_in(k, d)``                         -> ``k.fold(d)`` when
    ``d`` is a literal, else a per-site id

Identities are *deliberately* collision-ful: two ``split``s of the same
key produce identical subkeys in reality, so they map to identical ids
here — and sampling (``random_bits``) the same id twice is exactly the
bug.  Findings:

  KEY_REUSED          — one key id sampled at two or more sites
  RNG_LOOP_INVARIANT  — a key sampled inside a scan/while body while
                        loop-invariant there (a const, or a carry slot the
                        body passes through unchanged): every iteration
                        draws the same randomness.  The fix pattern is
                        ``fold_in(key, i)`` with the loop index — the fold
                        output is varying, so folded keys pass.

Loop-variance is tracked per frame: scan/while consts enter their body as
invariant, xs slices as varying, and a carry slot is varying iff the body
does not return it unchanged (an incremented counter is varying; an
untouched key is not).  ``cond`` branches merge their sample counts by
max, since only one branch executes.
"""
from __future__ import annotations

import itertools
from collections import Counter, defaultdict

from repro.analysis.static.core import Finding, PassResult, Program

_PROPAGATE_RAW = ("squeeze", "reshape", "convert_element_type",
                  "broadcast_in_dim")


def _is_key_aval(aval) -> bool:
    return str(getattr(aval, "dtype", "")).startswith("key<")


class _Key:
    __slots__ = ("id",)

    def __init__(self, id):
        self.id = id


class _KeyArr:        # output of random_split: an array of sibling keys
    __slots__ = ("id",)

    def __init__(self, id):
        self.id = id


class _Raw:           # random_unwrap'd view: uint32 bits + an index trail
    __slots__ = ("id", "idx")

    def __init__(self, id, idx=()):
        self.id, self.idx = id, idx


class RngTracer:
    def __init__(self):
        self.samples = Counter()          # key id -> static sample sites
        self.sites = defaultdict(list)    # key id -> [path, ...]
        self.invariant = {}               # key id -> first offending path
        self._fresh = itertools.count()
        self._site = itertools.count()
        self._wrap_memo = {}

    # -- id derivation ----------------------------------------------------
    def fresh(self, tag):
        return f"{tag}#{next(self._fresh)}"

    def _read(self, env, atom):
        from jax.extend import core
        if isinstance(atom, core.Literal):
            return ("lit", atom.val)
        return env.get(atom)

    def _varying(self, varying, atom):
        from jax.extend import core
        return (not isinstance(atom, core.Literal)) and atom in varying

    # -- the walk ---------------------------------------------------------
    def trace(self, closed_jaxpr):
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        consts = getattr(closed_jaxpr, "consts", ())
        env, varying = {}, set()
        # Every input gets a stable identity — keys often enter as raw
        # uint32[..,2] and get random_wrap'd per consumer, so the raw view
        # must carry the identity for two wraps of one arg to collide.
        for i, v in enumerate(jaxpr.invars):
            env[v] = (_Key(f"arg{i}") if _is_key_aval(v.aval)
                      else _Raw(f"arg{i}"))
        for i, cv in enumerate(jaxpr.constvars):
            env[cv] = (_Key(f"const{i}") if _is_key_aval(
                getattr(cv, "aval", None)) else _Raw(f"const{i}"))
        self._walk(jaxpr, env, varying, 0, "")
        return self

    def _walk(self, jaxpr, env, varying, loop_depth, path):
        from jax.extend import core
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            sub_path = f"{path}/{prim}" if path else prim
            handler = getattr(self, f"_h_{prim}", None)
            if handler is not None:
                handler(eqn, env, varying, loop_depth, sub_path)
                continue
            if prim in ("jit", "closed_call", "core_call", "remat",
                        "checkpoint", "remat2", "custom_jvp_call",
                        "custom_vjp_call", "custom_vjp_call_jaxpr",
                        "custom_jvp_call_jaxpr", "shard_map"):
                self._h_call(eqn, env, varying, loop_depth, sub_path)
                continue
            if prim == "scan":
                self._h_scan(eqn, env, varying, loop_depth, sub_path)
                continue
            if prim == "while":
                self._h_while(eqn, env, varying, loop_depth, sub_path)
                continue
            if prim == "cond":
                self._h_cond(eqn, env, varying, loop_depth, sub_path)
                continue
            # default: propagate raw views through shape-only ops, taint
            # outputs varying if any input is
            in_var = any(self._varying(varying, a) for a in eqn.invars)
            if prim in _PROPAGATE_RAW:
                val = self._read(env, eqn.invars[0])
                if isinstance(val, _Raw):
                    env[eqn.outvars[0]] = val
            elif prim in ("slice", "dynamic_slice"):
                val = self._read(env, eqn.invars[0])
                if isinstance(val, _Raw):
                    if prim == "slice":
                        idx = tuple(eqn.params.get("start_indices", ()))[:1]
                    else:
                        start = self._read(env, eqn.invars[1])
                        idx = ((start[1],) if isinstance(start, tuple) and
                               start[0] == "lit" else
                               (f"?{next(self._site)}",))
                    env[eqn.outvars[0]] = _Raw(val.id, val.idx + idx)
            if in_var:
                varying.update(eqn.outvars)

    # -- RNG primitive handlers -------------------------------------------
    def _h_random_seed(self, eqn, env, varying, depth, path):
        env[eqn.outvars[0]] = _Key(self.fresh("seed"))
        self._taint(eqn, varying)

    def _h_random_wrap(self, eqn, env, varying, depth, path):
        val = self._read(env, eqn.invars[0])
        if isinstance(val, _Raw):
            idx = "".join(f"[{i}]" for i in val.idx)
            env[eqn.outvars[0]] = _Key(f"{val.id}{idx}")
        elif isinstance(val, (_Key, _KeyArr)):
            env[eqn.outvars[0]] = _Key(val.id)
        else:
            # untracked bits: memoize per source var so wrapping the same
            # var twice still yields one identity
            atom = eqn.invars[0]
            wid = self._wrap_memo.setdefault(id(atom), self.fresh("wrap"))
            env[eqn.outvars[0]] = _Key(wid)
        self._taint(eqn, varying)

    def _h_random_unwrap(self, eqn, env, varying, depth, path):
        val = self._read(env, eqn.invars[0])
        if isinstance(val, _Key):
            env[eqn.outvars[0]] = _Raw(val.id)
        elif isinstance(val, _KeyArr):
            env[eqn.outvars[0]] = _Raw(f"{val.id}")
        self._taint(eqn, varying)

    def _h_random_split(self, eqn, env, varying, depth, path):
        val = self._read(env, eqn.invars[0])
        parent = val.id if isinstance(val, _Key) else self.fresh("split-src")
        env[eqn.outvars[0]] = _KeyArr(f"{parent}.split")
        self._taint(eqn, varying)

    def _h_random_fold_in(self, eqn, env, varying, depth, path):
        val = self._read(env, eqn.invars[0])
        parent = val.id if isinstance(val, _Key) else self.fresh("fold-src")
        data = self._read(env, eqn.invars[1])
        if isinstance(data, tuple) and data and data[0] == "lit":
            child = f"{parent}.fold({data[1]})"
        else:
            child = f"{parent}.fold(?{next(self._site)})"
        env[eqn.outvars[0]] = _Key(child)
        self._taint(eqn, varying)

    def _h_random_bits(self, eqn, env, varying, depth, path):
        val = self._read(env, eqn.invars[0])
        if isinstance(val, (_Key, _KeyArr)):
            self.samples[val.id] += 1
            self.sites[val.id].append(path)
            if depth >= 1 and not self._varying(varying, eqn.invars[0]):
                self.invariant.setdefault(val.id, path)
        self._taint(eqn, varying)

    def _taint(self, eqn, varying):
        if any(self._varying(varying, a) for a in eqn.invars):
            varying.update(eqn.outvars)

    # -- control flow ------------------------------------------------------
    @staticmethod
    def _sub_jaxpr(eqn):
        for k in ("jaxpr", "call_jaxpr"):
            if k in eqn.params:
                j = eqn.params[k]
                return getattr(j, "jaxpr", j), getattr(j, "consts", ())
        return None, ()

    def _bind(self, outer_env, outer_varying, outer_atoms, inner_vars,
              *, invariant=False):
        """Map outer atoms onto a sub-jaxpr's invars (aligned from the END,
        so prepended consts in the outer eqn don't shift the mapping)."""
        env, varying = {}, set()
        n = min(len(outer_atoms), len(inner_vars))
        for atom, var in zip(outer_atoms[-n:], inner_vars[-n:]):
            val = self._read(outer_env, atom)
            if isinstance(val, (_Key, _KeyArr, _Raw)) or \
                    (isinstance(val, tuple) and val and val[0] == "lit"):
                env[var] = val
            if not invariant and self._varying(outer_varying, atom):
                varying.add(var)
        return env, varying

    def _h_call(self, eqn, env, varying, depth, path):
        sub, consts = self._sub_jaxpr(eqn)
        if sub is None:
            return
        sub_env, sub_varying = self._bind(env, varying, eqn.invars,
                                          sub.invars)
        for cv in sub.constvars:
            if _is_key_aval(getattr(cv, "aval", None)):
                sub_env[cv] = _Key(self.fresh("const"))
        self._walk(sub, sub_env, sub_varying, depth, path)
        for outer, inner in zip(eqn.outvars, sub.outvars):
            from jax.extend import core
            if isinstance(inner, core.Var):
                val = sub_env.get(inner)
                if isinstance(val, (_Key, _KeyArr, _Raw)):
                    env[outer] = val
                if inner in sub_varying:
                    varying.add(outer)

    @staticmethod
    def _carry_passthrough(body, n_consts, n_carry):
        """Per carry slot: does the body return the very same var it was
        given?  (Then the slot is loop-invariant.)"""
        out = []
        for i in range(n_carry):
            out.append(body.outvars[i] is body.invars[n_consts + i])
        return out

    def _loop_body(self, eqn, env, varying, depth, path, body, n_consts,
                   n_carry, carry_atoms, xs_atoms):
        sub_env, sub_varying = {}, set()
        # consts: invariant inside the loop
        for atom, var in zip(eqn.invars[:n_consts], body.invars[:n_consts]):
            val = self._read(env, atom)
            if isinstance(val, (_Key, _KeyArr, _Raw)):
                sub_env[var] = val
        # carry: invariant iff passed through unchanged by the body
        passthrough = self._carry_passthrough(body, n_consts, n_carry)
        for i, (atom, var) in enumerate(zip(
                carry_atoms, body.invars[n_consts:n_consts + n_carry])):
            val = self._read(env, atom)
            if isinstance(val, (_Key, _KeyArr, _Raw)):
                sub_env[var] = val
            if not passthrough[i]:
                sub_varying.add(var)
        # xs: a fresh slice every iteration -> varying; a split array yields
        # one sibling key per step
        for atom, var in zip(xs_atoms, body.invars[n_consts + n_carry:]):
            val = self._read(env, atom)
            if isinstance(val, _KeyArr):
                sub_env[var] = _Key(f"{val.id}[xs]")
            elif isinstance(val, _Raw):
                sub_env[var] = val
            sub_varying.add(var)
        for cv in body.constvars:
            if _is_key_aval(getattr(cv, "aval", None)):
                sub_env[cv] = _Key(self.fresh("const"))
        self._walk(body, sub_env, sub_varying, depth + 1, path)

    def _h_scan(self, eqn, env, varying, depth, path):
        body = eqn.params["jaxpr"]
        body = getattr(body, "jaxpr", body)
        nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
        self._loop_body(eqn, env, varying, depth, path, body, nc, ncar,
                        eqn.invars[nc:nc + ncar], eqn.invars[nc + ncar:])

    def _h_while(self, eqn, env, varying, depth, path):
        body = eqn.params["body_jaxpr"]
        body = getattr(body, "jaxpr", body)
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        carry_atoms = eqn.invars[cn + bn:]
        # body invars = body_consts + carry; fake an eqn-invar prefix of just
        # the body consts by slicing past the cond consts
        class _E:  # minimal view with the right invars for _loop_body
            invars = eqn.invars[cn:cn + bn] + list(carry_atoms)
        self._loop_body(_E, env, varying, depth, path, body, bn,
                        len(carry_atoms), carry_atoms, [])

    def _h_cond(self, eqn, env, varying, depth, path):
        operands = eqn.invars[1:]
        saved = self.samples
        branch_counts = []
        for bi, br in enumerate(eqn.params["branches"]):
            sub = getattr(br, "jaxpr", br)
            sub_env, sub_varying = self._bind(env, varying, operands,
                                              sub.invars)
            for cv in sub.constvars:
                if _is_key_aval(getattr(cv, "aval", None)):
                    sub_env[cv] = _Key(self.fresh("const"))
            self.samples = Counter()
            self._walk(sub, sub_env, sub_varying, depth,
                       f"{path}[branch{bi}]")
            branch_counts.append(self.samples)
        self.samples = saved
        merged = Counter()
        for bc in branch_counts:
            for k, n in bc.items():
                merged[k] = max(merged[k], n)
        self.samples.update(merged)


class RngPass:
    name = "rng"

    def run(self, program: Program) -> PassResult:
        roles = [r for r in ("step", "fwd") if r in program.jaxprs]
        if not roles:
            return PassResult(self.name, program.name, [], skipped=True,
                              skip_reason="no jaxpr captured")
        findings, stats = [], {}
        for role in roles[:1]:   # step subsumes fwd; analyze the widest
            tr = RngTracer().trace(program.jaxprs[role])
            for key_id, n in sorted(tr.samples.items()):
                if n < 2:
                    continue
                # remat replay is intentional reuse: the recompute inside a
                # remat2 region samples the same key at the same logical
                # site, so two sites that differ only by remat2 frames are
                # one site
                norm = {"/".join(s for s in p.split("/") if s != "remat2")
                        for p in tr.sites[key_id]}
                if len(norm) >= 2:
                    findings.append(Finding(
                        self.name, "KEY_REUSED", "error", program.name,
                        f"{role}: key {key_id} sampled at {len(norm)} sites "
                        f"— correlated randomness: {sorted(norm)[:4]}",
                        detail={"role": role, "key": key_id,
                                "n_sites": len(norm),
                                "sites": tr.sites[key_id][:8]},
                        detail_key={"role": role, "key": key_id}))
            for key_id, where in sorted(tr.invariant.items()):
                findings.append(Finding(
                    self.name, "RNG_LOOP_INVARIANT", "error", program.name,
                    f"{role}: key {key_id} sampled inside a loop body while "
                    f"loop-invariant ({where}): every iteration draws the "
                    "same randomness; fold_in the loop index first",
                    detail={"role": role, "key": key_id, "where": where},
                    detail_key={"role": role, "key": key_id}))
            stats[role] = {"keys_sampled": len(tr.samples),
                           "total_sample_sites": sum(tr.samples.values())}
        return PassResult(self.name, program.name, findings, stats=stats)
