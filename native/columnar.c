/* _siddhi_native — C hot path for host-side event marshalling.
 *
 * Role in the framework: the TPU compute path is JAX/XLA; the host runtime
 * around it (ingestion marshalling, string interning) is native, mirroring
 * how the reference's performance-critical event plumbing is engineered
 * (reference: core/event/stream/converter/ — ZeroStreamEventConverter etc.,
 * and the Disruptor ring's event translation, StreamJunction.java:149-182).
 *
 * encode_rows() converts a Python list of row tuples into pre-allocated
 * columnar numpy buffers (via the buffer protocol — no numpy C-API
 * dependency), interning strings through the SAME dict/list pair that backs
 * the Python StringTable, so native and Python encode paths share one code
 * space and snapshot/restore stays unchanged.
 *
 * intern_column() interns a whole column (the send_columns and wire paths)
 * through the same pair, with two caches in front of the dict: a
 * pointer-identity memo for producers that pool their string objects, and
 * a table keyed on each str's UTF-8 bytes, probed with the interpreter
 * released. Both hold permanent codes only and are read-through: a miss
 * falls through to the dict, in value order, so every code is the one the
 * plain loop gives.
 *
 * Type codes (one byte per attribute):
 *   'b' bool -> int8 buffer      'i' int -> int32
 *   'l' long -> int64            'f' float -> float32
 *   'd' double -> float64        's' string -> int32 (interned code)
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <stdint.h>

/* Intern one string through (to_code: dict, to_str: list); returns code or -1
 * on error. None encodes as 0 (null). `transient` (may be NULL) is the
 * StringTable's transient-code dict: a LIVE transient string (a uuid coming
 * back from a client) must round-trip to its transient code, or device
 * equality against stored uuid columns would never match — and permanently
 * interning it would shadow the transient code for every later encode(). */
static int32_t
intern_string(PyObject *value, PyObject *to_code, PyObject *to_str,
              PyObject *transient)
{
    if (value == Py_None)
        return 0;
    PyObject *existing = PyDict_GetItemWithError(to_code, value);
    if (existing != NULL)
        return (int32_t)PyLong_AsLong(existing);
    if (PyErr_Occurred())
        return -1;
    if (transient != NULL && transient != Py_None) {
        existing = PyDict_GetItemWithError(transient, value);
        if (existing != NULL)
            return (int32_t)PyLong_AsLong(existing);
        if (PyErr_Occurred())
            return -1;
    }
    Py_ssize_t code = PyList_GET_SIZE(to_str);
    PyObject *code_obj = PyLong_FromSsize_t(code);
    if (code_obj == NULL)
        return -1;
    if (PyDict_SetItem(to_code, value, code_obj) < 0 ||
        PyList_Append(to_str, value) < 0) {
        Py_DECREF(code_obj);
        return -1;
    }
    Py_DECREF(code_obj);
    return (int32_t)code;
}

/* encode_rows(rows, typecodes: bytes, columns: tuple[memoryview-able],
 *             tables: tuple[(dict, list) | None], nulls: tuple[float|int]) */
static PyObject *
encode_rows(PyObject *self, PyObject *args)
{
    PyObject *rows, *typecodes_obj, *columns, *tables, *nulls;
    if (!PyArg_ParseTuple(args, "OSOOO", &rows, &typecodes_obj, &columns,
                          &tables, &nulls))
        return NULL;

    const char *typecodes = PyBytes_AS_STRING(typecodes_obj);
    Py_ssize_t n_cols = PyBytes_GET_SIZE(typecodes_obj);

    if (!PyTuple_Check(columns) || PyTuple_GET_SIZE(columns) < n_cols ||
        !PyTuple_Check(tables) || PyTuple_GET_SIZE(tables) < n_cols ||
        !PyTuple_Check(nulls) || PyTuple_GET_SIZE(nulls) < n_cols) {
        PyErr_SetString(PyExc_TypeError,
                        "columns/tables/nulls must be tuples of arity >= "
                        "len(typecodes)");
        return NULL;
    }

    PyObject *rows_fast = PySequence_Fast(rows, "rows must be a sequence");
    if (rows_fast == NULL)
        return NULL;
    Py_ssize_t n_rows = PySequence_Fast_GET_SIZE(rows_fast);

    /* acquire writable buffers for every column */
    Py_buffer *bufs = PyMem_Calloc((size_t)n_cols, sizeof(Py_buffer));
    if (bufs == NULL) {
        Py_DECREF(rows_fast);
        return PyErr_NoMemory();
    }
    Py_ssize_t acquired = 0;
    PyObject *result = NULL;
    for (; acquired < n_cols; acquired++) {
        PyObject *col = PyTuple_GET_ITEM(columns, acquired);
        if (PyObject_GetBuffer(col, &bufs[acquired],
                               PyBUF_WRITABLE | PyBUF_C_CONTIGUOUS) < 0)
            goto done;
        /* capacity check: a short buffer would mean silent heap corruption
         * where the pure-Python fallback raises IndexError */
        static const Py_ssize_t width[128] = {
            ['b'] = 1, ['i'] = 4, ['l'] = 8, ['f'] = 4, ['d'] = 8, ['s'] = 4};
        char tc = typecodes[acquired];
        Py_ssize_t w = ((unsigned char)tc < 128) ? width[(int)tc] : 0;
        if (w == 0) {
            PyErr_Format(PyExc_ValueError, "bad type code %c", tc);
            acquired++; /* this buffer was acquired; release it in done */
            goto done;
        }
        if (bufs[acquired].len < n_rows * w) {
            PyErr_Format(PyExc_ValueError,
                         "column %zd buffer too small: %zd bytes for %zd "
                         "rows of width %zd", acquired, bufs[acquired].len,
                         n_rows, w);
            acquired++;
            goto done;
        }
        if (tc == 's') {
            PyObject *pair = PyTuple_GET_ITEM(tables, acquired);
            if (!PyTuple_Check(pair) || PyTuple_GET_SIZE(pair) < 2 ||
                PyTuple_GET_SIZE(pair) > 3 ||
                !PyDict_Check(PyTuple_GET_ITEM(pair, 0)) ||
                !PyList_Check(PyTuple_GET_ITEM(pair, 1)) ||
                (PyTuple_GET_SIZE(pair) == 3 &&
                 !PyDict_Check(PyTuple_GET_ITEM(pair, 2)))) {
                PyErr_Format(PyExc_TypeError,
                             "tables[%zd] must be (dict, list[, transient "
                             "dict]) for a string column", acquired);
                acquired++;
                goto done;
            }
        }
    }

    for (Py_ssize_t r = 0; r < n_rows; r++) {
        PyObject *row = PySequence_Fast_GET_ITEM(rows_fast, r);
        PyObject *row_fast = PySequence_Fast(row, "row must be a sequence");
        if (row_fast == NULL)
            goto done;
        if (PySequence_Fast_GET_SIZE(row_fast) < n_cols) {
            Py_DECREF(row_fast);
            PyErr_Format(PyExc_ValueError,
                         "row %zd has fewer than %zd values", r, n_cols);
            goto done;
        }
        for (Py_ssize_t c = 0; c < n_cols; c++) {
            PyObject *v = PySequence_Fast_GET_ITEM(row_fast, c);
            void *data = bufs[c].buf;
            char tc = typecodes[c];
            if (tc == 's') {
                PyObject *pair = PyTuple_GET_ITEM(tables, c);
                int32_t code = intern_string(
                    v, PyTuple_GET_ITEM(pair, 0), PyTuple_GET_ITEM(pair, 1),
                    PyTuple_GET_SIZE(pair) == 3 ? PyTuple_GET_ITEM(pair, 2)
                                                : NULL);
                if (code < 0 && PyErr_Occurred()) {
                    Py_DECREF(row_fast);
                    goto done;
                }
                ((int32_t *)data)[r] = code;
                continue;
            }
            int is_null = (v == Py_None);
            if (is_null)
                v = PyTuple_GET_ITEM(nulls, c);
            switch (tc) {
            case 'b': {
                int x = PyObject_IsTrue(v);
                if (x < 0) { Py_DECREF(row_fast); goto done; }
                ((int8_t *)data)[r] = (int8_t)x;
                break;
            }
            case 'i': {
                long x = PyLong_AsLong(v);
                if (x == -1 && PyErr_Occurred()) { Py_DECREF(row_fast); goto done; }
                ((int32_t *)data)[r] = (int32_t)x;
                break;
            }
            case 'l': {
                long long x = PyLong_AsLongLong(v);
                if (x == -1 && PyErr_Occurred()) { Py_DECREF(row_fast); goto done; }
                ((int64_t *)data)[r] = (int64_t)x;
                break;
            }
            case 'f': {
                double x = PyFloat_AsDouble(v);
                if (x == -1.0 && PyErr_Occurred()) { Py_DECREF(row_fast); goto done; }
                ((float *)data)[r] = (float)x;
                break;
            }
            case 'd': {
                double x = PyFloat_AsDouble(v);
                if (x == -1.0 && PyErr_Occurred()) { Py_DECREF(row_fast); goto done; }
                ((double *)data)[r] = x;
                break;
            }
            default:
                Py_DECREF(row_fast);
                PyErr_Format(PyExc_ValueError, "bad type code %c", tc);
                goto done;
            }
        }
        Py_DECREF(row_fast);
    }
    result = Py_NewRef(Py_None);

done:
    for (Py_ssize_t i = 0; i < acquired; i++)
        PyBuffer_Release(&bufs[i]);
    PyMem_Free(bufs);
    Py_DECREF(rows_fast);
    return result;
}

/* fill_ts(ts_list, out: int64 buffer, n_pad) — timestamps + monotone pad */
static PyObject *
fill_ts(PyObject *self, PyObject *args)
{
    PyObject *ts_list, *out;
    Py_ssize_t n_pad;
    if (!PyArg_ParseTuple(args, "OOn", &ts_list, &out, &n_pad))
        return NULL;
    PyObject *fast = PySequence_Fast(ts_list, "ts must be a sequence");
    if (fast == NULL)
        return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    Py_buffer buf;
    if (PyObject_GetBuffer(out, &buf, PyBUF_WRITABLE | PyBUF_C_CONTIGUOUS) < 0) {
        Py_DECREF(fast);
        return NULL;
    }
    if (buf.len < n_pad * (Py_ssize_t)sizeof(int64_t) ||
        buf.len < n * (Py_ssize_t)sizeof(int64_t)) {
        PyErr_Format(PyExc_ValueError,
                     "ts buffer too small: %zd bytes for %zd entries",
                     buf.len, (n_pad > n) ? n_pad : n);
        PyBuffer_Release(&buf);
        Py_DECREF(fast);
        return NULL;
    }
    int64_t *data = (int64_t *)buf.buf;
    int64_t last = 0;
    for (Py_ssize_t i = 0; i < n; i++) {
        long long x = PyLong_AsLongLong(PySequence_Fast_GET_ITEM(fast, i));
        if (x == -1 && PyErr_Occurred()) {
            PyBuffer_Release(&buf);
            Py_DECREF(fast);
            return NULL;
        }
        data[i] = (int64_t)x;
        last = (int64_t)x;
    }
    for (Py_ssize_t i = n; i < n_pad; i++)
        data[i] = last; /* monotone pad keeps searchsorted correct */
    PyBuffer_Release(&buf);
    Py_DECREF(fast);
    return Py_NewRef(Py_None);
}

/* --- pointer-identity intern memo -------------------------------------
 *
 * Producers that pool their string objects (a symbol universe, a parsed
 * dictionary — the common shape for market-data/telemetry feeds) send the
 * SAME PyObject* for a value over and over. A bounded open-addressing map
 * keyed on object identity turns the per-value PyDict_GetItem (hash every
 * character) into a pointer compare (~4 ns). Entries hold STRONG refs, so
 * a pointer can never be recycled for a different string while memoized;
 * only PERMANENT codes are memoized (append-only, never reassigned) —
 * transient uuid-ring codes recycle and must not be cached. The memo is
 * dropped wholesale on StringTable.restore (codes reassigned there). */

#define IDMEMO_BITS 13
#define IDMEMO_SIZE (1 << IDMEMO_BITS)  /* 8192 slots/attr, ~96 KB */

typedef struct {
    PyObject *keys[IDMEMO_SIZE]; /* strong refs or NULL */
    int32_t codes[IDMEMO_SIZE];
} id_memo;

static void
idmemo_capsule_destruct(PyObject *capsule)
{
    id_memo *m = (id_memo *)PyCapsule_GetPointer(capsule, "siddhi.idmemo");
    if (m == NULL)
        return;
    for (Py_ssize_t i = 0; i < IDMEMO_SIZE; i++)
        Py_XDECREF(m->keys[i]);
    PyMem_Free(m);
}

/* idmemo_new() -> capsule */
static PyObject *
idmemo_new(PyObject *self, PyObject *args)
{
    id_memo *m = PyMem_Calloc(1, sizeof(id_memo));
    if (m == NULL)
        return PyErr_NoMemory();
    return PyCapsule_New(m, "siddhi.idmemo", idmemo_capsule_destruct);
}

static inline size_t
idmemo_slot(PyObject *p)
{
    /* low bits of the pointer are alignment zeros; Fibonacci-mix the rest */
    return (size_t)(((uintptr_t)p >> 4) * (uintptr_t)0x9E3779B97F4A7C15ULL
                    >> (64 - IDMEMO_BITS));
}

/* memoize a permanent code (transient ring codes recycle): prefer an empty
 * probe slot, else evict the second */
static void
idmemo_put(id_memo *m, PyObject *v, int32_t code)
{
    size_t slot = idmemo_slot(v);
    size_t slot2 = (slot + 1) & (IDMEMO_SIZE - 1); /* one probe step */
    if (m->keys[slot] == v || m->keys[slot2] == v)
        return;
    size_t s = (m->keys[slot] == NULL) ? slot : slot2;
    Py_XDECREF(m->keys[s]);
    m->keys[s] = Py_NewRef(v);
    m->codes[s] = code;
}

/* --- byte-keyed intern table ------------------------------------------
 *
 * A read-through cache of StringTable._to_code keyed on a str's UTF-8
 * bytes, so that a lookup needs neither the interpreter nor the `str`'s
 * Python hash: a wire frame's dictionary entries are new objects every
 * frame, so their hashes are never cached and the pointer memo above never
 * hits them. Never the master: every entry is a permanent code that
 * intern_string returned for those bytes (codes are append-only, so an
 * entry never goes stale), a miss falls through to the dict, and the
 * table is dropped wholesale on StringTable.restore. Open addressing,
 * linear probing on a 64-bit hash, doubled at a load of one half; a key
 * of up to ST_INLINE bytes lives in its slot, a longer one in `keys`.
 *
 * `lock` orders the probe, which runs with the interpreter released,
 * against inserts and growth. It is never held across Python code, and no
 * thread waits for it while holding the interpreter (st_lock), so it
 * cannot deadlock with the interpreter's lock. */

#define ST_INLINE 16
#define ST_BLOCK 32        /* values hashed and prefetched together */
#define ST_NOGIL_MIN 1024  /* fewer probes than this keep the interpreter:
                            * releasing it would cost more than it frees */
#define TRANSIENT_BASE (1 << 30)

typedef struct {
    uint64_t h;
    int32_t code;          /* 0: empty (code 0 is null, never cached) */
    uint32_t len;
    union {
        char b[ST_INLINE];
        size_t off;        /* into `keys`, for a longer key */
    } key;
} st_slot;

typedef struct {
    st_slot *slots;
    size_t mask;           /* slot count - 1 */
    size_t count;
    char *keys;
    size_t keys_len, keys_cap;
    PyThread_type_lock lock;
} intern_table;

static void
intern_table_destruct(PyObject *capsule)
{
    intern_table *t = (intern_table *)PyCapsule_GetPointer(
        capsule, "siddhi.interntable");
    if (t == NULL)
        return;
    PyMem_Free(t->slots);
    PyMem_Free(t->keys);
    PyThread_free_lock(t->lock);
    PyMem_Free(t);
}

/* intern_table_new() -> capsule */
static PyObject *
intern_table_new(PyObject *self, PyObject *args)
{
    intern_table *t = PyMem_Calloc(1, sizeof(intern_table));
    if (t == NULL)
        return PyErr_NoMemory();
    t->mask = 1023;
    t->slots = PyMem_Calloc(t->mask + 1, sizeof(st_slot));
    t->lock = PyThread_allocate_lock();
    if (t->slots == NULL || t->lock == NULL) {
        PyMem_Free(t->slots);
        if (t->lock != NULL)
            PyThread_free_lock(t->lock);
        PyMem_Free(t);
        return PyErr_NoMemory();
    }
    return PyCapsule_New(t, "siddhi.interntable", intern_table_destruct);
}

static uint64_t
st_hash(const char *p, size_t len)
{
    const uint64_t k0 = 0x9E3779B97F4A7C15ULL, k1 = 0xC2B2AE3D27D4EB4FULL;
    uint64_t h = (uint64_t)len * k1, v;
    for (; len > 8; p += 8, len -= 8) {
        memcpy(&v, p, 8);
        h = (h ^ v) * k0;
        h ^= h >> 29;
    }
    v = 0;
    memcpy(&v, p, len);
    h = (h ^ v) * k0;
    h ^= h >> 32;
    h *= k1;
    return h ^ (h >> 29);
}

static inline const char *
st_key(const intern_table *t, const st_slot *s)
{
    return s->len <= ST_INLINE ? s->key.b : t->keys + s->key.off;
}

/* the code cached for these bytes, or 0 */
static inline int32_t
st_find(const intern_table *t, uint64_t h, const char *p, size_t len)
{
    for (size_t i = (size_t)h & t->mask;; i = (i + 1) & t->mask) {
        const st_slot *s = &t->slots[i];
        if (s->code == 0)
            return 0;
        if (s->h == h && s->len == len && memcmp(st_key(t, s), p, len) == 0)
            return s->code;
    }
}

static inline st_slot *
st_empty_slot(st_slot *slots, size_t mask, uint64_t h)
{
    size_t i = (size_t)h & mask;
    while (slots[i].code != 0)
        i = (i + 1) & mask;
    return &slots[i];
}

/* cache `code` for these bytes; an allocation that fails leaves the key
 * out (the table is a cache: the dict still has it). Caller holds `lock`. */
static void
st_insert(intern_table *t, uint64_t h, const char *p, size_t len,
          int32_t code)
{
    if (len > UINT32_MAX || st_find(t, h, p, len) != 0)
        return;
    if (2 * (t->count + 1) > t->mask + 1) {
        size_t mask = 2 * t->mask + 1;
        st_slot *slots = PyMem_Calloc(mask + 1, sizeof(st_slot));
        if (slots == NULL)
            return;
        for (size_t i = 0; i <= t->mask; i++)
            if (t->slots[i].code != 0)
                *st_empty_slot(slots, mask, t->slots[i].h) = t->slots[i];
        PyMem_Free(t->slots);
        t->slots = slots;
        t->mask = mask;
    }
    size_t off = 0;
    if (len > ST_INLINE) {
        if (t->keys_len + len > t->keys_cap) {
            size_t cap = 2 * t->keys_cap + len + 4096;
            char *keys = PyMem_Realloc(t->keys, cap);
            if (keys == NULL)
                return;
            t->keys = keys;
            t->keys_cap = cap;
        }
        off = t->keys_len;
        memcpy(t->keys + off, p, len);
        t->keys_len += len;
    }
    st_slot *s = st_empty_slot(t->slots, t->mask, h);
    s->h = h;
    s->len = (uint32_t)len;
    if (len > ST_INLINE)
        s->key.off = off;
    else
        memcpy(s->key.b, p, len);
    s->code = code;
    t->count++;
}

/* take `lock` while holding the interpreter: wait for it with the
 * interpreter released, as its holder may be a probe that needs it next */
static void
st_lock(intern_table *t)
{
    if (!PyThread_acquire_lock(t->lock, NOWAIT_LOCK)) {
        Py_BEGIN_ALLOW_THREADS
        PyThread_acquire_lock(t->lock, WAIT_LOCK);
        Py_END_ALLOW_THREADS
    }
}

/* one value still to resolve after phase 1 */
typedef struct {
    uint64_t h;
    size_t off;            /* its bytes in the call's own copy */
    Py_ssize_t len;        /* -1: not a key (not an exact str, or not
                            * encodable as UTF-8) */
    Py_ssize_t idx;        /* its position in the column */
} st_probe;

/* Phase 2: look every keyed value up, ST_BLOCK at a time — hash the block
 * and prefetch its slots, then compare. Hits go to `out`; the rest are
 * compacted, in value order, to the front of `pr`. Returns how many are
 * left; `*hits` is how many the table resolved. Reads only the table and
 * the call's own memory: safe without the interpreter. */
static Py_ssize_t
st_probe_all(const intern_table *t, st_probe *pr, Py_ssize_t n,
             const char *bytes, int32_t *out, Py_ssize_t *hits)
{
    Py_ssize_t left = 0, found = 0;
    for (Py_ssize_t b = 0; b < n; b += ST_BLOCK) {
        Py_ssize_t e = (n - b > ST_BLOCK) ? b + ST_BLOCK : n;
        for (Py_ssize_t k = b; k < e; k++)
            if (pr[k].len >= 0) {
                pr[k].h = st_hash(bytes + pr[k].off, (size_t)pr[k].len);
                __builtin_prefetch(&t->slots[pr[k].h & t->mask]);
            }
        for (Py_ssize_t k = b; k < e; k++) {
            int32_t code = pr[k].len < 0 ? 0
                : st_find(t, pr[k].h, bytes + pr[k].off, (size_t)pr[k].len);
            if (code != 0) {
                out[pr[k].idx] = code;
                found++;
            } else {
                pr[left++] = pr[k];
            }
        }
    }
    *hits = found;
    return left;
}

/* intern_column(values, out: int32 buffer, to_code: dict, to_str: list,
 *               transient: dict, memo_capsule, table_capsule) -> int
 *
 * Vectorized string interning for one column (the send_columns and wire
 * paths); `transient` keeps live uuid codes stable. Returns how many
 * values the byte-keyed table resolved. Three phases:
 *   1. with the interpreter: None -> 0, the pointer memo, and each other
 *      exact str's UTF-8 bytes copied into the call's own buffer;
 *   2. the table probe (above), with the interpreter released when there
 *      are at least ST_NOGIL_MIN values to probe;
 *   3. with the interpreter: the rest through intern_string in value order
 *      — so every code is the one the plain loop would give — then their
 *      permanent codes into the memo and the table.
 * A str that is not UTF-8 (a lone surrogate) is simply not a key. */
static PyObject *
intern_column(PyObject *self, PyObject *args)
{
    PyObject *values, *out, *to_code, *to_str, *transient;
    PyObject *memo_capsule, *table_capsule;
    if (!PyArg_ParseTuple(args, "OOO!O!O!OO", &values, &out,
                          &PyDict_Type, &to_code, &PyList_Type, &to_str,
                          &PyDict_Type, &transient, &memo_capsule,
                          &table_capsule))
        return NULL;
    id_memo *memo = (id_memo *)PyCapsule_GetPointer(memo_capsule,
                                                    "siddhi.idmemo");
    if (memo == NULL)
        return NULL;
    intern_table *table = (intern_table *)PyCapsule_GetPointer(
        table_capsule, "siddhi.interntable");
    if (table == NULL)
        return NULL;
    PyObject *fast = PySequence_Fast(values, "values must be a sequence");
    if (fast == NULL)
        return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    Py_buffer buf;
    if (PyObject_GetBuffer(out, &buf, PyBUF_WRITABLE | PyBUF_C_CONTIGUOUS) < 0) {
        Py_DECREF(fast);
        return NULL;
    }
    PyObject *result = NULL;
    int32_t *data = (int32_t *)buf.buf;
    st_probe *pr = NULL;
    char *bytes = NULL;
    size_t bytes_len = 0, bytes_cap = 0;
    Py_ssize_t left = 0, hits = 0, held = 0;
    /* phase 3's values whose codes go into the table, held until then */
    PyObject **held_v = NULL;
    int32_t *held_code = NULL;
    if (buf.len < n * (Py_ssize_t)sizeof(int32_t)) {
        PyErr_SetString(PyExc_ValueError, "intern_column: out buffer too small");
        goto done;
    }
    pr = PyMem_Malloc((n > 0 ? (size_t)n : 1) * sizeof(st_probe));
    if (pr == NULL) {
        PyErr_NoMemory();
        goto done;
    }

    /* phase 1 */
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *v = PySequence_Fast_GET_ITEM(fast, i);
        if (v == Py_None) {
            data[i] = 0;
            continue;
        }
        size_t slot = idmemo_slot(v);
        size_t slot2 = (slot + 1) & (IDMEMO_SIZE - 1);
        if (memo->keys[slot] == v || memo->keys[slot2] == v) {
            data[i] = memo->codes[memo->keys[slot] == v ? slot : slot2];
            continue;
        }
        st_probe *e = &pr[left++];
        e->idx = i;
        e->off = 0;
        e->len = -1;
        if (!PyUnicode_CheckExact(v))
            continue;
        Py_ssize_t len;
        const char *p = PyUnicode_AsUTF8AndSize(v, &len);
        if (p == NULL) {
            PyErr_Clear();
            continue;
        }
        if (bytes_len + (size_t)len > bytes_cap) {
            size_t cap = 2 * bytes_cap + (size_t)len + 4096;
            char *grown = PyMem_Realloc(bytes, cap);
            if (grown == NULL) {
                PyErr_NoMemory();
                goto done;
            }
            bytes = grown;
            bytes_cap = cap;
        }
        memcpy(bytes + bytes_len, p, (size_t)len);
        e->off = bytes_len;
        e->len = len;
        bytes_len += (size_t)len;
    }

    /* phase 2 */
    if (left >= ST_NOGIL_MIN) {
        Py_BEGIN_ALLOW_THREADS
        PyThread_acquire_lock(table->lock, WAIT_LOCK);
        left = st_probe_all(table, pr, left, bytes, data, &hits);
        PyThread_release_lock(table->lock);
        Py_END_ALLOW_THREADS
    } else if (left > 0) {
        st_lock(table);
        left = st_probe_all(table, pr, left, bytes, data, &hits);
        PyThread_release_lock(table->lock);
    }

    /* phase 3: the rest in value order, then their permanent codes into
     * the memo and the table. No Python code runs under `lock`: the
     * resolution (which may compare arbitrary objects) comes first. */
    if (left > 0) {
        held_v = PyMem_Malloc((size_t)left * sizeof(PyObject *));
        held_code = PyMem_Malloc((size_t)left * sizeof(int32_t));
        if (held_v == NULL || held_code == NULL) {
            PyErr_NoMemory();
            goto done;
        }
    }
    for (Py_ssize_t k = 0; k < left; k++) {
        Py_ssize_t i = pr[k].idx;
        if (i >= PySequence_Fast_GET_SIZE(fast)) {
            PyErr_SetString(PyExc_RuntimeError,
                            "intern_column: values changed size");
            goto done;
        }
        PyObject *v = PySequence_Fast_GET_ITEM(fast, i);
        int32_t code = intern_string(v, to_code, to_str, transient);
        if (code < 0 && PyErr_Occurred())
            goto done;
        data[i] = code;
        if (v == Py_None || code <= 0 || code >= TRANSIENT_BASE)
            continue;
        idmemo_put(memo, v, code);
        if (PyUnicode_CheckExact(v)) {
            held_v[held] = Py_NewRef(v);
            held_code[held++] = code;
        }
    }
    if (held > 0) {
        st_lock(table);
        for (Py_ssize_t k = 0; k < held; k++) {
            Py_ssize_t len;
            const char *p = PyUnicode_AsUTF8AndSize(held_v[k], &len);
            if (p == NULL)
                PyErr_Clear();
            else
                st_insert(table, st_hash(p, (size_t)len), p, (size_t)len,
                          held_code[k]);
        }
        PyThread_release_lock(table->lock);
    }
    result = PyLong_FromSsize_t(hits);

done:
    for (Py_ssize_t k = 0; k < held; k++)
        Py_DECREF(held_v[k]);
    PyMem_Free(held_v);
    PyMem_Free(held_code);
    PyMem_Free(pr);
    PyMem_Free(bytes);
    PyBuffer_Release(&buf);
    Py_DECREF(fast);
    return result;
}

/* map_codes(codes: int32 buffer, to_str: list) -> list[str|None]
 * — vectorized string-column decode; out-of-range codes map to None (the
 *   caller pre-screens transient codes and takes the Python path). */
static PyObject *
map_codes(PyObject *self, PyObject *args)
{
    PyObject *codes, *to_str;
    if (!PyArg_ParseTuple(args, "OO!", &codes, &PyList_Type, &to_str))
        return NULL;
    Py_buffer buf;
    if (PyObject_GetBuffer(codes, &buf, PyBUF_C_CONTIGUOUS) < 0)
        return NULL;
    Py_ssize_t n = buf.len / (Py_ssize_t)sizeof(int32_t);
    Py_ssize_t table_n = PyList_GET_SIZE(to_str);
    const int32_t *data = (const int32_t *)buf.buf;
    PyObject *result = PyList_New(n);
    if (result == NULL) {
        PyBuffer_Release(&buf);
        return NULL;
    }
    for (Py_ssize_t i = 0; i < n; i++) {
        int32_t c = data[i];
        PyObject *v = (c >= 0 && c < table_n) ? PyList_GET_ITEM(to_str, c)
                                              : Py_None;
        PyList_SET_ITEM(result, i, Py_NewRef(v));
    }
    PyBuffer_Release(&buf);
    return result;
}

/* decode_dict(buf: bytes-like, offset, dict_n) -> (values: list[str], end)
 *
 * One SXF1 string column's dictionary block (io/wire.py): dict_n entries,
 * each a little-endian u16 byte length and that many UTF-8 bytes, from
 * `offset` in `buf`; `end` is the offset after the last entry. The wire
 * decode runs in the HTTP handler's thread and holds the interpreter for as
 * long as it lasts: 123k entries cost the Python loop ~60 ms of it, this
 * one a few. Every header and body is checked against the buffer, and
 * dict_n against what the buffer could hold before the list is made;
 * ValueError (UnicodeDecodeError for bytes that are not UTF-8) otherwise. */
static PyObject *
decode_dict(PyObject *self, PyObject *args)
{
    Py_buffer buf;
    Py_ssize_t off, dict_n;
    if (!PyArg_ParseTuple(args, "y*nn", &buf, &off, &dict_n))
        return NULL;
    const unsigned char *data = (const unsigned char *)buf.buf;
    Py_ssize_t total = buf.len;
    PyObject *values = NULL;
    if (off < 0 || off > total || dict_n < 0 || dict_n > (total - off) / 2) {
        /* an entry is at least its two-byte header */
        PyErr_Format(PyExc_ValueError,
                     "dictionary of %zd entries cannot fit in the %zd bytes "
                     "after offset %zd", dict_n, total - off, off);
        goto fail;
    }
    values = PyList_New(dict_n);
    if (values == NULL)
        goto fail;
    for (Py_ssize_t i = 0; i < dict_n; i++) {
        if (total - off < 2) {
            PyErr_Format(PyExc_ValueError,
                         "dictionary entry %zd of %zd: header runs past the "
                         "end of the payload", i, dict_n);
            goto fail;
        }
        Py_ssize_t blen = (Py_ssize_t)data[off]
                          | ((Py_ssize_t)data[off + 1] << 8);
        off += 2;
        if (total - off < blen) {
            PyErr_Format(PyExc_ValueError,
                         "dictionary entry %zd of %zd: %zd bytes run past "
                         "the end of the payload", i, dict_n, blen);
            goto fail;
        }
        PyObject *s = PyUnicode_DecodeUTF8((const char *)data + off, blen,
                                           "strict");
        if (s == NULL)
            goto fail;
        PyList_SET_ITEM(values, i, s);
        off += blen;
    }
    PyBuffer_Release(&buf);
    return Py_BuildValue("(Nn)", values, off);
fail:
    Py_XDECREF(values);
    PyBuffer_Release(&buf);
    return NULL;
}

/* build_events(event_cls, ts: int64 buffer, expired: uint8 buffer,
 *              cols: tuple[list]) -> list[Event]
 *
 * Decode hot loop: allocates Event instances via tp_alloc and fills the
 * three fields through their (pre-fetched) slot descriptors — bypassing
 * __init__ cuts per-event cost ~5x, which is the difference between the
 * public callback path keeping up with the device and not. */
static PyObject *
build_events(PyObject *self, PyObject *args)
{
    PyObject *cls_obj, *ts_obj, *exp_obj, *cols;
    if (!PyArg_ParseTuple(args, "OOOO!", &cls_obj, &ts_obj, &exp_obj,
                          &PyTuple_Type, &cols))
        return NULL;
    if (!PyType_Check(cls_obj)) {
        PyErr_SetString(PyExc_TypeError, "event_cls must be a type");
        return NULL;
    }
    PyTypeObject *cls = (PyTypeObject *)cls_obj;
    Py_ssize_t n_cols = PyTuple_GET_SIZE(cols);

    Py_buffer ts_buf, exp_buf;
    if (PyObject_GetBuffer(ts_obj, &ts_buf, PyBUF_C_CONTIGUOUS) < 0)
        return NULL;
    if (PyObject_GetBuffer(exp_obj, &exp_buf, PyBUF_C_CONTIGUOUS) < 0) {
        PyBuffer_Release(&ts_buf);
        return NULL;
    }
    Py_ssize_t n = ts_buf.len / (Py_ssize_t)sizeof(int64_t);
    PyObject *result = NULL, *d_ts = NULL, *d_data = NULL, *d_exp = NULL;
    if (exp_buf.len < n) {
        PyErr_SetString(PyExc_ValueError, "expired buffer shorter than ts");
        goto fail;
    }
    for (Py_ssize_t c = 0; c < n_cols; c++) {
        PyObject *col = PyTuple_GET_ITEM(cols, c);
        if (!PyList_Check(col) || PyList_GET_SIZE(col) < n) {
            PyErr_Format(PyExc_ValueError,
                         "cols[%zd] must be a list of >= %zd items", c, n);
            goto fail;
        }
    }
    d_ts = PyObject_GetAttrString(cls_obj, "timestamp");
    d_data = PyObject_GetAttrString(cls_obj, "data");
    d_exp = PyObject_GetAttrString(cls_obj, "is_expired");
    if (!d_ts || !d_data || !d_exp)
        goto fail;
    descrsetfunc set_ts = Py_TYPE(d_ts)->tp_descr_set;
    descrsetfunc set_data = Py_TYPE(d_data)->tp_descr_set;
    descrsetfunc set_exp = Py_TYPE(d_exp)->tp_descr_set;
    if (!set_ts || !set_data || !set_exp) {
        PyErr_SetString(PyExc_TypeError,
                        "event_cls fields must be slot descriptors");
        goto fail;
    }
    const int64_t *ts_data = (const int64_t *)ts_buf.buf;
    const uint8_t *exp_data = (const uint8_t *)exp_buf.buf;

    result = PyList_New(n);
    if (result == NULL)
        goto fail;
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *data = PyTuple_New(n_cols);
        if (data == NULL)
            goto fail_clear;
        for (Py_ssize_t c = 0; c < n_cols; c++) {
            PyObject *v = PyList_GET_ITEM(PyTuple_GET_ITEM(cols, c), i);
            PyTuple_SET_ITEM(data, c, Py_NewRef(v));
        }
        PyObject *ev = cls->tp_alloc(cls, 0);
        if (ev == NULL) {
            Py_DECREF(data);
            goto fail_clear;
        }
        PyObject *ts_val = PyLong_FromLongLong((long long)ts_data[i]);
        if (ts_val == NULL ||
            set_ts(d_ts, ev, ts_val) < 0 ||
            set_data(d_data, ev, data) < 0 ||
            set_exp(d_exp, ev, exp_data[i] ? Py_True : Py_False) < 0) {
            Py_XDECREF(ts_val);
            Py_DECREF(data);
            Py_DECREF(ev);
            goto fail_clear;
        }
        Py_DECREF(ts_val);
        Py_DECREF(data); /* slot holds its own reference */
        /* untrack from the cyclic GC: events hold only a tuple of scalars /
         * strings (no cycles possible), and tracking millions of short-lived
         * objects makes gen-0 collections the decode bottleneck */
        if (PyObject_GC_IsTracked(data))
            PyObject_GC_UnTrack(data);
        if (PyObject_GC_IsTracked(ev))
            PyObject_GC_UnTrack(ev);
        PyList_SET_ITEM(result, i, ev);
    }
    goto done;

fail_clear:
    Py_CLEAR(result);
fail:
done:
    Py_XDECREF(d_ts);
    Py_XDECREF(d_data);
    Py_XDECREF(d_exp);
    PyBuffer_Release(&ts_buf);
    PyBuffer_Release(&exp_buf);
    return result;
}

/* ------------------------------------------------------------------------
 * MPSC staging ring — the Disruptor's role (reference:
 * core/stream/StreamJunction.java:279-316 ring buffer + worker consumers).
 *
 * Producers (source threads / user send) claim slots with a C11 atomic
 * fetch-add and publish with a per-slot sequence stamp; one consumer (the
 * junction's feeder thread) drains batches. Correct for true concurrent
 * producers (the design does not lean on the GIL for the index protocol;
 * the PyObject* payloads themselves are only touched under the GIL, which
 * every Python-level producer and the feeder hold at the call boundary).
 * ---------------------------------------------------------------------- */

#include <stdatomic.h>

#include "colring_core.h"

typedef struct {
    Py_ssize_t cap;
    atomic_size_t head;       /* next slot to claim (producers) */
    size_t tail;              /* next slot to read (single consumer) */
    atomic_size_t *seq;       /* published when seq[i % cap] == i + 1 */
    PyObject **rows;          /* owned references */
    int64_t *ts;
} mpsc_ring;

static void
ring_capsule_destruct(PyObject *capsule)
{
    mpsc_ring *r = (mpsc_ring *)PyCapsule_GetPointer(capsule, "siddhi.ring");
    if (r == NULL)
        return;
    for (size_t i = r->tail; i < atomic_load(&r->head); i++) {
        size_t s = i % (size_t)r->cap;
        if (atomic_load(&r->seq[s]) == i + 1)
            Py_XDECREF(r->rows[s]);
    }
    PyMem_Free(r->seq);
    PyMem_Free(r->rows);
    PyMem_Free(r->ts);
    PyMem_Free(r);
}

/* ring_new(capacity) -> capsule */
static PyObject *
ring_new(PyObject *self, PyObject *args)
{
    Py_ssize_t cap;
    if (!PyArg_ParseTuple(args, "n", &cap))
        return NULL;
    if (cap < 1) {
        PyErr_SetString(PyExc_ValueError, "ring capacity must be >= 1");
        return NULL;
    }
    mpsc_ring *r = PyMem_Calloc(1, sizeof(mpsc_ring));
    if (r == NULL)
        return PyErr_NoMemory();
    r->cap = cap;
    atomic_init(&r->head, 0);
    r->tail = 0;
    r->seq = PyMem_Calloc((size_t)cap, sizeof(atomic_size_t));
    r->rows = PyMem_Calloc((size_t)cap, sizeof(PyObject *));
    r->ts = PyMem_Calloc((size_t)cap, sizeof(int64_t));
    if (!r->seq || !r->rows || !r->ts) {
        PyMem_Free(r->seq); PyMem_Free(r->rows); PyMem_Free(r->ts);
        PyMem_Free(r);
        return PyErr_NoMemory();
    }
    return PyCapsule_New(r, "siddhi.ring", ring_capsule_destruct);
}

static mpsc_ring *
ring_of(PyObject *capsule)
{
    return (mpsc_ring *)PyCapsule_GetPointer(capsule, "siddhi.ring");
}

/* ring_push(ring, ts, row) -> bool (False = full, caller applies
 * backpressure like the Disruptor's blocking wait) */
static PyObject *
ring_push(PyObject *self, PyObject *args)
{
    PyObject *capsule, *row;
    long long ts;
    if (!PyArg_ParseTuple(args, "OLO", &capsule, &ts, &row))
        return NULL;
    mpsc_ring *r = ring_of(capsule);
    if (r == NULL)
        return NULL;
    size_t cap = (size_t)r->cap;
    size_t claimed = atomic_load(&r->head);
    for (;;) {
        if (claimed - r->tail >= cap)
            Py_RETURN_FALSE; /* full */
        if (atomic_compare_exchange_weak(&r->head, &claimed, claimed + 1))
            break;
    }
    size_t s = claimed % cap;
    Py_INCREF(row);
    r->rows[s] = row;
    r->ts[s] = (int64_t)ts;
    atomic_store(&r->seq[s], claimed + 1); /* publish */
    Py_RETURN_TRUE;
}

/* ring_pop_batch(ring, max_n) -> (ts_list, row_list) — single consumer */
static PyObject *
ring_pop_batch(PyObject *self, PyObject *args)
{
    PyObject *capsule;
    Py_ssize_t max_n;
    if (!PyArg_ParseTuple(args, "On", &capsule, &max_n))
        return NULL;
    mpsc_ring *r = ring_of(capsule);
    if (r == NULL)
        return NULL;
    PyObject *ts_list = PyList_New(0);
    PyObject *row_list = PyList_New(0);
    if (!ts_list || !row_list) {
        Py_XDECREF(ts_list);
        Py_XDECREF(row_list);
        return NULL;
    }
    size_t cap = (size_t)r->cap;
    for (Py_ssize_t n = 0; n < max_n; n++) {
        size_t i = r->tail;
        size_t s = i % cap;
        if (atomic_load(&r->seq[s]) != i + 1)
            break; /* not yet published (or empty) */
        PyObject *ts_obj = PyLong_FromLongLong((long long)r->ts[s]);
        if (ts_obj == NULL || PyList_Append(ts_list, ts_obj) < 0 ||
            PyList_Append(row_list, r->rows[s]) < 0) {
            Py_XDECREF(ts_obj);
            Py_DECREF(ts_list);
            Py_DECREF(row_list);
            return NULL;
        }
        Py_DECREF(ts_obj);
        Py_DECREF(r->rows[s]);
        r->rows[s] = NULL;
        atomic_store(&r->seq[s], 0);
        r->tail = i + 1;
    }
    return Py_BuildValue("(NN)", ts_list, row_list);
}

/* ring_size(ring) -> int (published, unconsumed entries; approximate
 * under concurrent producers) */
static PyObject *
ring_size(PyObject *self, PyObject *args)
{
    PyObject *capsule;
    if (!PyArg_ParseTuple(args, "O", &capsule))
        return NULL;
    mpsc_ring *r = ring_of(capsule);
    if (r == NULL)
        return NULL;
    return PyLong_FromSize_t(atomic_load(&r->head) - r->tail);
}

/* ------------------------------------------------------------------------
 * Lock-free multi-producer COLUMNAR ring — the zero-copy ingress stage.
 *
 * Where the MPSC ring above stages PyObject* rows (decoded under the GIL by
 * the feeder), this ring stages raw columnar bytes: fixed-width native
 * buffers, one per attribute (string attrs as pre-interned int32 dictionary
 * codes). Producers claim a contiguous run of slots with one CAS
 * (claim-then-write, Disruptor-style, so parallel encode workers can fill
 * their runs out of order while consumption stays in claim order), write
 * with the GIL RELEASED (the payload is plain memory — memcpy needs no
 * interpreter), and publish per-slot sequence stamps. One consumer copies
 * contiguous published runs out into caller buffers, also without the GIL.
 *
 * The claim/publish/consume protocol itself lives in colring_core.h (pure
 * C11, no Python.h) so native/colring_stress.c can compile the IDENTICAL
 * code under TSan/ASan/UBSan; these wrappers own arg parsing, Py_buffer
 * handling, payload memcpy, and the GIL.
 * ---------------------------------------------------------------------- */

#define COLRING_MAX_COLS 64

typedef struct {
    crc_ring rc;              /* claim/publish protocol (colring_core.h) */
    int n_cols;
    Py_ssize_t widths[COLRING_MAX_COLS];
    char *cols[COLRING_MAX_COLS];   /* cap * width bytes each */
    int64_t *ts;
} colring;

static void
colring_capsule_destruct(PyObject *capsule)
{
    colring *r = (colring *)PyCapsule_GetPointer(capsule, "siddhi.colring");
    if (r == NULL)
        return;
    for (int c = 0; c < r->n_cols; c++)
        PyMem_Free(r->cols[c]);
    PyMem_Free(r->ts);
    PyMem_Free(r->rc.seq);
    PyMem_Free(r);
}

static Py_ssize_t
colring_width(char tc)
{
    switch (tc) {
    case 'b': return 1;
    case 'i': return 4;
    case 'l': return 8;
    case 'f': return 4;
    case 'd': return 8;
    case 's': return 4;  /* pre-interned int32 dictionary codes */
    default:  return 0;
    }
}

/* colring_new(capacity, typecodes: bytes) -> capsule */
static PyObject *
colring_new(PyObject *self, PyObject *args)
{
    Py_ssize_t cap_req;
    PyObject *typecodes_obj;
    if (!PyArg_ParseTuple(args, "nS", &cap_req, &typecodes_obj))
        return NULL;
    if (cap_req < 1) {
        PyErr_SetString(PyExc_ValueError, "colring capacity must be >= 1");
        return NULL;
    }
    Py_ssize_t n_cols = PyBytes_GET_SIZE(typecodes_obj);
    if (n_cols > COLRING_MAX_COLS) {
        PyErr_Format(PyExc_ValueError, "colring supports at most %d columns",
                     COLRING_MAX_COLS);
        return NULL;
    }
    size_t cap = 1;
    while (cap < (size_t)cap_req)
        cap <<= 1;
    colring *r = PyMem_Calloc(1, sizeof(colring));
    if (r == NULL)
        return PyErr_NoMemory();
    r->n_cols = (int)n_cols;
    const char *tcs = PyBytes_AS_STRING(typecodes_obj);
    r->ts = PyMem_Malloc(cap * sizeof(int64_t));
    crc_init(&r->rc, PyMem_Calloc(cap, sizeof(crc_seq)), cap);
    if (r->ts == NULL || r->rc.seq == NULL) {
        PyErr_NoMemory();
        goto fail;
    }
    for (Py_ssize_t c = 0; c < n_cols; c++) {
        Py_ssize_t w = colring_width(tcs[c]);
        if (w == 0) {
            PyErr_Format(PyExc_ValueError, "bad type code %c", tcs[c]);
            goto fail;
        }
        r->widths[c] = w;
        r->cols[c] = PyMem_Malloc(cap * (size_t)w);
        if (r->cols[c] == NULL) {
            PyErr_NoMemory();
            goto fail;
        }
    }
    return PyCapsule_New(r, "siddhi.colring", colring_capsule_destruct);

fail:
    for (Py_ssize_t k = 0; k < n_cols; k++)
        PyMem_Free(r->cols[k]);  /* calloc'd struct: unset slots are NULL */
    PyMem_Free(r->ts);
    PyMem_Free(r->rc.seq);
    PyMem_Free(r);
    return NULL;
}

static colring *
colring_of(PyObject *capsule)
{
    return (colring *)PyCapsule_GetPointer(capsule, "siddhi.colring");
}

/* colring_claim(ring, n) -> start index, or -1 when the ring lacks n free
 * slots (all-or-nothing; the caller spins/backpressures). One CAS claims
 * the whole contiguous run — claim order IS delivery order, which is what
 * makes parallel out-of-order encode workers deterministic downstream. */
static PyObject *
colring_claim(PyObject *self, PyObject *args)
{
    PyObject *capsule;
    Py_ssize_t n;
    if (!PyArg_ParseTuple(args, "On", &capsule, &n))
        return NULL;
    colring *r = colring_of(capsule);
    if (r == NULL)
        return NULL;
    if (n < 1 || (size_t)n > r->rc.cap) {
        PyErr_Format(PyExc_ValueError,
                     "colring_claim: n=%zd out of range (cap %zu)",
                     n, r->rc.cap);
        return NULL;
    }
    ptrdiff_t start = crc_claim(&r->rc, (size_t)n);
    if (start < 0)
        return PyLong_FromLong(-1); /* insufficient free space */
    return PyLong_FromUnsignedLongLong((unsigned long long)start);
}

/* colring_write(ring, start, n, ts_buf: int64[n], cols: tuple[buffer]) —
 * copy one claimed run into the ring and publish it. The copies run with
 * the GIL released; string columns arrive here already interned to int32
 * codes (interning is the only stage that still batch-acquires the GIL,
 * in the worker pool above this). */
static PyObject *
colring_write(PyObject *self, PyObject *args)
{
    PyObject *capsule, *ts_obj, *cols;
    unsigned long long start;
    Py_ssize_t n;
    if (!PyArg_ParseTuple(args, "OKnOO!", &capsule, &start, &n, &ts_obj,
                          &PyTuple_Type, &cols))
        return NULL;
    colring *r = colring_of(capsule);
    if (r == NULL)
        return NULL;
    if (PyTuple_GET_SIZE(cols) != r->n_cols) {
        PyErr_Format(PyExc_ValueError, "colring_write: expected %d columns",
                     r->n_cols);
        return NULL;
    }
    Py_buffer ts_buf;
    Py_buffer bufs[COLRING_MAX_COLS];
    if (PyObject_GetBuffer(ts_obj, &ts_buf, PyBUF_C_CONTIGUOUS) < 0)
        return NULL;
    if (ts_buf.len < n * (Py_ssize_t)sizeof(int64_t)) {
        PyErr_SetString(PyExc_ValueError, "colring_write: ts buffer short");
        PyBuffer_Release(&ts_buf);
        return NULL;
    }
    int acquired = 0;
    for (; acquired < r->n_cols; acquired++) {
        if (PyObject_GetBuffer(PyTuple_GET_ITEM(cols, acquired),
                               &bufs[acquired], PyBUF_C_CONTIGUOUS) < 0)
            goto fail;
        if (bufs[acquired].len < n * r->widths[acquired]) {
            PyErr_Format(PyExc_ValueError,
                         "colring_write: column %d buffer short", acquired);
            acquired++;
            goto fail;
        }
    }
    Py_BEGIN_ALLOW_THREADS
    {
        size_t s0 = (size_t)start & r->rc.mask;
        size_t first = r->rc.cap - s0;       /* slots before wrap */
        if (first > (size_t)n)
            first = (size_t)n;
        size_t second = (size_t)n - first;
        memcpy(r->ts + s0, ts_buf.buf, first * sizeof(int64_t));
        if (second)
            memcpy(r->ts, (const int64_t *)ts_buf.buf + first,
                   second * sizeof(int64_t));
        for (int c = 0; c < r->n_cols; c++) {
            size_t w = (size_t)r->widths[c];
            const char *src = (const char *)bufs[c].buf;
            memcpy(r->cols[c] + s0 * w, src, first * w);
            if (second)
                memcpy(r->cols[c], src + first * w, second * w);
        }
        /* publish AFTER the data: crc_publish's release stores pair with
         * the consumer's acquire loads, slot by slot */
        crc_publish(&r->rc, (size_t)start, (size_t)n);
    }
    Py_END_ALLOW_THREADS
    for (int i = 0; i < acquired; i++)
        PyBuffer_Release(&bufs[i]);
    PyBuffer_Release(&ts_buf);
    Py_RETURN_NONE;

fail:
    for (int i = 0; i < acquired; i++)
        PyBuffer_Release(&bufs[i]);
    PyBuffer_Release(&ts_buf);
    return NULL;
}

/* colring_pop(ring, max_n, ts_out: int64 buffer, cols_out: tuple[buffer])
 * -> n copied (0 when nothing contiguous is published). Single consumer. */
static PyObject *
colring_pop(PyObject *self, PyObject *args)
{
    PyObject *capsule, *ts_obj, *cols;
    Py_ssize_t max_n;
    if (!PyArg_ParseTuple(args, "OnOO!", &capsule, &max_n, &ts_obj,
                          &PyTuple_Type, &cols))
        return NULL;
    colring *r = colring_of(capsule);
    if (r == NULL)
        return NULL;
    if (PyTuple_GET_SIZE(cols) != r->n_cols) {
        PyErr_Format(PyExc_ValueError, "colring_pop: expected %d columns",
                     r->n_cols);
        return NULL;
    }
    Py_buffer ts_buf;
    Py_buffer bufs[COLRING_MAX_COLS];
    if (PyObject_GetBuffer(ts_obj, &ts_buf,
                           PyBUF_WRITABLE | PyBUF_C_CONTIGUOUS) < 0)
        return NULL;
    int acquired = 0;
    for (; acquired < r->n_cols; acquired++) {
        if (PyObject_GetBuffer(PyTuple_GET_ITEM(cols, acquired),
                               &bufs[acquired],
                               PyBUF_WRITABLE | PyBUF_C_CONTIGUOUS) < 0)
            goto fail;
    }
    /* bound max_n by the output buffers up front */
    if (ts_buf.len / (Py_ssize_t)sizeof(int64_t) < max_n)
        max_n = ts_buf.len / (Py_ssize_t)sizeof(int64_t);
    for (int c = 0; c < r->n_cols; c++)
        if (bufs[c].len / r->widths[c] < max_n)
            max_n = bufs[c].len / r->widths[c];
    if (max_n < 0)
        max_n = 0;
    size_t n = crc_poll(&r->rc, (size_t)max_n);
    if (n > 0) {
        Py_BEGIN_ALLOW_THREADS
        {
            size_t t = atomic_load_explicit(&r->rc.tail,
                                            memory_order_relaxed);
            size_t s0 = t & r->rc.mask;
            size_t first = r->rc.cap - s0;
            if (first > n)
                first = n;
            size_t second = n - first;
            memcpy(ts_buf.buf, r->ts + s0, first * sizeof(int64_t));
            if (second)
                memcpy((int64_t *)ts_buf.buf + first, r->ts,
                       second * sizeof(int64_t));
            for (int c = 0; c < r->n_cols; c++) {
                size_t w = (size_t)r->widths[c];
                char *dst = (char *)bufs[c].buf;
                memcpy(dst, r->cols[c] + s0 * w, first * w);
                if (second)
                    memcpy(dst + first * w, r->cols[c], second * w);
            }
            crc_consume(&r->rc, n);
        }
        Py_END_ALLOW_THREADS
    }
    for (int i = 0; i < acquired; i++)
        PyBuffer_Release(&bufs[i]);
    PyBuffer_Release(&ts_buf);
    return PyLong_FromSize_t(n);

fail:
    for (int i = 0; i < acquired; i++)
        PyBuffer_Release(&bufs[i]);
    PyBuffer_Release(&ts_buf);
    return NULL;
}

/* colring_size(ring) -> claimed, unconsumed depth (approximate under
 * concurrent producers; includes claimed-but-unwritten runs) */
static PyObject *
colring_size(PyObject *self, PyObject *args)
{
    PyObject *capsule;
    if (!PyArg_ParseTuple(args, "O", &capsule))
        return NULL;
    colring *r = colring_of(capsule);
    if (r == NULL)
        return NULL;
    return PyLong_FromSize_t(crc_size(&r->rc));
}

/* colring_capacity(ring) -> rounded power-of-two slot count */
static PyObject *
colring_capacity(PyObject *self, PyObject *args)
{
    PyObject *capsule;
    if (!PyArg_ParseTuple(args, "O", &capsule))
        return NULL;
    colring *r = colring_of(capsule);
    if (r == NULL)
        return NULL;
    return PyLong_FromSize_t(r->rc.cap);
}

/* colring_hwm(ring) -> claimed-depth high-water mark over the ring's life */
static PyObject *
colring_hwm(PyObject *self, PyObject *args)
{
    PyObject *capsule;
    if (!PyArg_ParseTuple(args, "O", &capsule))
        return NULL;
    colring *r = colring_of(capsule);
    if (r == NULL)
        return NULL;
    return PyLong_FromSize_t(crc_hwm(&r->rc));
}

static PyMethodDef methods[] = {
    {"encode_rows", encode_rows, METH_VARARGS,
     "Encode row tuples into columnar buffers with string interning."},
    {"fill_ts", fill_ts, METH_VARARGS,
     "Fill an int64 timestamp buffer with monotone padding."},
    {"idmemo_new", idmemo_new, METH_VARARGS,
     "idmemo_new() -> capsule: pointer-identity intern memo"},
    {"intern_table_new", intern_table_new, METH_VARARGS,
     "intern_table_new() -> capsule: byte-keyed cache of permanent codes"},
    {"intern_column", intern_column, METH_VARARGS,
     "Intern a string column into an int32 code buffer; returns table hits."},
    {"map_codes", map_codes, METH_VARARGS,
     "Decode an int32 code buffer through a string table list."},
    {"decode_dict", decode_dict, METH_VARARGS,
     "Decode an SXF1 dictionary block into (list[str], end offset)."},
    {"build_events", build_events, METH_VARARGS,
     "Construct a list of Event objects from decoded columns."},
    {"ring_new", ring_new, METH_VARARGS,
     "Create an MPSC staging ring of (ts, row) slots."},
    {"ring_push", ring_push, METH_VARARGS,
     "Push one (ts, row); returns False when full (backpressure)."},
    {"ring_pop_batch", ring_pop_batch, METH_VARARGS,
     "Drain up to max_n published entries (single consumer)."},
    {"ring_size", ring_size, METH_VARARGS,
     "Published, unconsumed entry count."},
    {"colring_new", colring_new, METH_VARARGS,
     "Create a lock-free multi-producer columnar ring (capacity, typecodes)."},
    {"colring_claim", colring_claim, METH_VARARGS,
     "CAS-claim n contiguous slots; returns start index or -1 when full."},
    {"colring_write", colring_write, METH_VARARGS,
     "Copy a claimed run's ts+columns into the ring and publish (GIL released)."},
    {"colring_pop", colring_pop, METH_VARARGS,
     "Copy the contiguous published prefix out (single consumer, GIL released)."},
    {"colring_size", colring_size, METH_VARARGS,
     "Claimed, unconsumed slot count."},
    {"colring_capacity", colring_capacity, METH_VARARGS,
     "Rounded power-of-two slot capacity."},
    {"colring_hwm", colring_hwm, METH_VARARGS,
     "Claimed-depth high-water mark."},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef module = {
    PyModuleDef_HEAD_INIT, "_siddhi_native",
    "Native host-path marshalling for siddhi_tpu.", -1, methods,
};

PyMODINIT_FUNC
PyInit__siddhi_native(void)
{
    return PyModule_Create(&module);
}
