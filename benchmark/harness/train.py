"""The one train driver: the program's `paddle.jit.to_static` train step
(forward, loss, backward, AdamW step) fed a new batch every step through
`paddle_tpu.io.DataLoader`, on one chip."""
from __future__ import annotations

import collections
import math

import numpy as np

from . import check, common, spec as _spec, weights as _weights
from .common import clock, log, span


class SeededRows:
    """Map-style dataset: row i is `seq_len` token ids drawn from (seed,
    i), so every row differs; labels are the next token (the row rolled
    left by one). Plain numpy: the reference reads the same rows."""

    def __init__(self, seed: int, seq_len: int, vocab: int, rows: int):
        self.seed, self.seq_len, self.vocab, self.rows = (
            int(seed), seq_len, vocab, rows)

    def __len__(self):
        return self.rows

    def __getitem__(self, i):
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF,
                                     self.seed >> 32, int(i)])
        ids = rng.integers(0, self.vocab, self.seq_len, dtype=np.int64)
        return ids, np.roll(ids, -1)

    def batch(self, k: int, size: int):
        """Batch k as the loader yields it (the reference's feed)."""
        rows = [self[i] for i in range(k * size, (k + 1) * size)]
        return (np.stack([r[0] for r in rows]),
                np.stack([r[1] for r in rows]))


def _norms(arrays, views, minus=None):
    """Frobenius norm of every piece `views[k]` = [index, ...] of
    `arrays[k]` (less `minus[k]`, where given), in one jitted call."""
    import jax
    import jax.numpy as jnp

    def fn(xs, ys):
        out = []
        for k, x in enumerate(xs):
            d = x.astype(jnp.float32)
            if ys is not None:
                d = d - ys[k].astype(jnp.float32)
            out += [jnp.sqrt(jnp.sum(jnp.square(d[idx])))
                    for idx in views[k]]
        return out

    return [float(x) for x in jax.jit(fn)(
        list(arrays), None if minus is None else list(minus))]


class Program:
    """The system under test, built once: the compiled step with its
    state. Set-up drives this object through its checked steps and hands
    the same object to the window."""

    def __init__(self, sp: _spec.Spec, seed: int):
        import paddle_tpu as paddle
        import paddle_tpu.optimizer as opt

        cell, cfg = sp.cell, sp.config
        self.sp, self.seed, self._paddle = sp, seed, paddle
        self.stack = sp.stack
        self.batch, self.seq = cell["batch"], cell["seq_len"]
        model = self.stack.build_model(cfg, recompute=cell["recompute"])
        log("the program's model object built (its own initialisation)")
        self.specs = self.stack.leaf_specs(cfg)
        self.names = [n for n, _ in model.named_parameters()]
        pieces = [self.stack.parts(n, tuple(p.shape))
                  for n, p in model.named_parameters()]
        self.views = [[idx for _, idx in ps] for ps in pieces]
        self.piece_names = [n + suffix for n, ps in zip(self.names, pieces)
                            for suffix, _ in ps]
        w = _weights.make_weights(self.specs, seed, cfg["dtype"])
        if sorted(w) != sorted(self.names):
            raise ValueError("the stack's leaf names are not the program's")
        for n, p in model.named_parameters():
            if tuple(p.shape) != tuple(w[n].shape):
                raise ValueError(f"leaf {n}: program {p.shape}, "
                                 f"seeded {w[n].shape}")
            p._replace_value(w[n])
        del w
        o = cell["optimizer"]
        self.optimizer = opt.AdamW(
            learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
            epsilon=o["eps"], weight_decay=o["weight_decay"],
            parameters=model.parameters(),
            multi_precision=o["multi_precision"])
        self.model = model
        optimizer = self.optimizer

        @paddle.jit.to_static(full_graph=True)
        def train_step(ids, labels):
            loss, _ = model(ids, labels=labels)
            loss.backward()
            optimizer.step()
            optimizer.clear_grad()
            return loss

        self.train_step = train_step
        self.dataset = SeededRows(seed, self.seq,
                                  self.stack.vocab_size(cfg), 1 << 18)
        from paddle_tpu.io import DataLoader

        self.feed = iter(DataLoader(self.dataset, batch_size=self.batch,
                                    shuffle=False, drop_last=True,
                                    num_workers=0))

    def next_batch(self):
        # the loader's Tensors hold numpy storage, which to_static's first
        # call refuses (PERF.md, Open questions): put them on the device
        ids, labels = (np.asarray(t._value) for t in next(self.feed))
        return self._paddle.to_tensor(ids), self._paddle.to_tensor(labels)

    def step(self):
        """One call of the timed path: the feed and the compiled step."""
        with span("bench.feed"):
            ids, labels = self.next_batch()
        with span("bench.train_step"):
            return self.train_step(ids, labels)

    def _state(self, store):
        return [store[id(p)] for _, p in self.model.named_parameters()]

    def grad_norms(self):
        """Each leaf's first-gradient norm as the optimizer got it: after
        one step from zero moments, moment1 = (1 - beta1) g."""
        b1 = self.sp.cell["optimizer"]["beta1"]
        m1 = self._state(self.optimizer._accumulators["moment1"])
        return dict(zip(self.piece_names,
                        (x / (1 - b1) for x in _norms(m1, self.views))))

    def change_norms(self):
        """Each leaf's norm of (master weight - seeded initial weight)."""
        init = _weights.make_weights(self.specs, self.seed,
                                     self.sp.config["dtype"])
        masters = self._state(self.optimizer._master_weights)
        return dict(zip(self.piece_names, _norms(
            masters, self.views, [init[n] for n in self.names])))

    def free(self):
        self.model = self.optimizer = self.train_step = self.feed = None
        common.free_device()


def checked_steps(prog: Program) -> dict:
    """Drive the program through its first steps by the window's own call
    and keep what the comparison reads."""
    n = prog.sp.cell["checked_steps"]
    losses = []
    for k in range(1, n + 1):
        losses.append(float(prog.step()))
        if k == 1:
            grad_norms = prog.grad_norms()
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": prog.change_norms()}


def reference_readings(sp: _spec.Spec, seed: int, *, precision="f32",
                       fault=None) -> dict:
    """The plain reference over the same weights and the same batches."""
    cfg, cell = sp.config, sp.cell
    stack = sp.stack
    ref = _spec.load_by_name("reference", stack.REFERENCE)
    data = SeededRows(seed, cell["seq_len"], stack.vocab_size(cfg), 1 << 18)
    batches = [data.batch(k, cell["batch"])
               for k in range(cell["checked_steps"])]
    make = lambda: _weights.make_weights(stack.leaf_specs(cfg), seed,
                                         cfg["dtype"])
    return ref.train(make, batches, cfg, cell["optimizer"],
                     precision=precision, fault=fault)


def run(sp: _spec.Spec, seed: int, seconds: float, trace_on: bool,
        device: dict, t_start: float, trace_seconds: float = 5.0) -> dict:
    from . import peaks as _peaks

    common.setup_program_cache()
    cell, cfg = sp.cell, sp.config
    log(f"{clock() - t_start:.1f} s since the process began: imports, "
        f"the look for the chip")
    prog = Program(sp, seed)
    log(f"{clock() - t_start:.1f} s: built {cfg['name']} on {device}")
    readings = checked_steps(prog)
    log(f"{clock() - t_start:.1f} s: checked steps, losses "
        f"{readings['losses']}")
    times = []
    for _ in range(cell["warm_steps"]):
        t = clock()
        float(prog.step())
        times.append(clock() - t)
    log(f"warm steps {[round(x * 1e3) for x in times]} ms")
    tracer = common.Tracer(sp.name)
    tokens_per_step = prog.batch * prog.seq

    # ---- the window -------------------------------------------------------
    # Two steps in flight, one sync at the end. A traced run syncs where
    # its untraced part ends (the rate that `mfu.train` reads is that
    # part's: starting the profiler stops this thread for seconds), then
    # traces `trace_seconds` more and closes before it stops the profiler.
    pending, losses, steps = collections.deque(), [], 0

    def drive(until):
        nonlocal steps
        while clock() < until:
            pending.append(prog.step())
            steps += 1
            if len(pending) > 2:
                with span("bench.sync"):
                    losses.append(float(pending.popleft()))
        with span("bench.sync"):
            losses.extend(float(x) for x in pending)
        pending.clear()

    setup_s = clock() - t_start
    t0 = clock()
    drive(t0 + (max(seconds - trace_seconds, 0.0) if trace_on else seconds))
    t1 = clock()
    window_s = t1 - t0
    tokens_per_s = steps * tokens_per_step / window_s
    if trace_on:
        tracer.start()
        drive(clock() + trace_seconds)
        tracer.stop()
    peak_bytes = common.memory_peak_bytes()
    finite = all(math.isfinite(x) for x in losses)
    log(f"window: {steps} steps in {window_s:.2f} s, {tokens_per_s:.0f} "
        f"tokens/s, peak {peak_bytes / 1e9:.2f} GB")

    # ---- the comparison, once the program's state is freed ------------------
    prog.free()
    log(f"freed: {common.bytes_in_use() / 1e9:.2f} GB still in use")
    t = clock()
    ref = reference_readings(sp, seed)
    log(f"reference: {clock() - t:.1f} s, losses {ref['losses']}")
    numbers, where = check.compare_train(readings, ref)
    ok, compared = check.verdict(numbers, cell["limits"], extra_ok=finite)

    pk = _peaks.peaks_for(device["kind"])
    ctx = {
        "spec": sp, "peaks": pk, "chips": sp.chips, "window_s": window_s,
        "tokens_per_s": tokens_per_s, "steps": steps,
        "tokens_per_step": tokens_per_step, "tracer": tracer,
    }
    return {
        "correct": ok, "attempted": steps, "failed": 0 if finite else 1,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "memory_peak_bytes": peak_bytes, "ctx": ctx,
        "compared": compared, "notes": where,
    }
