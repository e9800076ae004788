/* gtpu_wirepack: the wire pack of a sweep chunk, in one call.
 *
 * gatekeeper_tpu/parallel/sharded.py:pack_transfer_cols narrows a chunk's
 * per-object columns into one [pad_n, W] buffer per wire dtype.  Its numpy
 * form (pack_transfer_cols_py, which stays the reference, the fallback and
 * the path of a drifted chunk) makes four to nine passes over a column,
 * most through fresh int64 temporaries, and copies every part once more in
 * np.concatenate.  pack() reads each column once and writes its stored
 * form straight into buf[:, off:off + store_w] at the buffer's row stride,
 * and checks on the way what the numpy form checks before it may narrow:
 * every value inside the stored type, an elided column still the corpus
 * constant, membership in the corpus dictionary, integrality of a float
 * column.  It decides nothing: the plan (one step a
 * column) is built in Python from the corpus stats alone, and a chunk with
 * a failed step is packed again, whole, by the numpy form.
 *
 * Rows are walked in blocks, every step over one block before the next
 * block, so the destination rows of a block (a few hundred bytes an object
 * from some eighty columns) stay in cache while the columns land in them.
 *
 * One thread, no state between calls but the released clock; the GIL is
 * released once the steps are parsed and taken back when the last block
 * is written.
 *
 * The released clock: the CPU seconds the calling thread burnt between
 * Py_BEGIN_ALLOW_THREADS and Py_END_ALLOW_THREADS, on the clock of
 * time.thread_time(), kept per thread.  released_cpu() reads it, and
 * ops/native.released_thread_time() sums it with the columnizer's, so a
 * stage's account can say held = cpu - released (PERF.md section 3).
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <stdint.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>

enum { OP_CHECK = 0, OP_COPY, OP_BIAS, OP_NIBBLE, OP_DICT, N_OPS };
enum { K_I4 = 0, K_I8, K_I1, K_U1, K_F4, K_OTHER };

#define BLOCK_ROWS 256
#define DENSE_SPAN 65536 /* a dictionary this narrow gets a byte table */
#define HASH_BITS 9      /* 512 slots for at most 254 entries */
#define ABSENT 0xFF      /* no dictionary index: they end at 253 */

typedef struct step step_t;
typedef void (*kernel_t)(step_t *, Py_ssize_t, Py_ssize_t);

struct step {
    kernel_t fn;
    const char *src;    /* [n, w] items, C-contiguous */
    Py_ssize_t w;       /* source items a row */
    Py_ssize_t row_b;   /* source bytes a row */
    char *dst;          /* the column's first stored byte in row 0 */
    Py_ssize_t dstride; /* bytes from one destination row to the next */
    int64_t bias;
    uint64_t cap;       /* the largest biased value the stored type holds */
    uint64_t acc;       /* every biased value of an integer source, ORed */
    float flo, fhi;     /* CHECK: the constant twice; else -bias, cap - bias */
    int64_t ilo, ihi;   /* CHECK of an integer source: the constant */
    int64_t imn, imx;   /* ... and what the chunk holds */
    int bad;            /* a float out of range or fractional, a value the
                         * dictionary does not hold */
    uint8_t *dense;     /* dictionary: index by value - base, or */
    int64_t base;
    uint64_t span;
    int64_t *hkeys;     /* ... open addressing, hvals ABSENT where empty */
    uint8_t *hvals;
};

#define FOLD_RANGE(s, mn, mx)                                              \
    do {                                                                   \
        if ((int64_t)(mn) < (s)->imn)                                      \
            (s)->imn = (int64_t)(mn);                                      \
        if ((int64_t)(mx) > (s)->imx)                                      \
            (s)->imx = (int64_t)(mx);                                      \
    } while (0)

/* the range of rows [r0, r1) of an integer column, nothing stored */
#define DEF_CHECK(NAME, T)                                                 \
    static void NAME(step_t *s, Py_ssize_t r0, Py_ssize_t r1)              \
    {                                                                      \
        const T *p = (const T *)s->src + r0 * s->w;                        \
        const Py_ssize_t m = (r1 - r0) * s->w;                             \
        T mn = p[0], mx = p[0];                                            \
        for (Py_ssize_t i = 1; i < m; i++) {                               \
            T v = p[i];                                                    \
            mn = v < mn ? v : mn;                                          \
            mx = v > mx ? v : mx;                                          \
        }                                                                  \
        FOLD_RANGE(s, mn, mx);                                             \
    }

/* (a + bias).astype(O) for O uint8 or uint16.  A is unsigned and at
 * least as wide as T and 32 bits, so a value below -bias wraps to one
 * with high bits set: the OR of every biased value is inside the stored
 * type's all-ones cap exactly when each of them is. */
#define DEF_BIAS(NAME, T, A, O)                                            \
    static void NAME(step_t *s, Py_ssize_t r0, Py_ssize_t r1)              \
    {                                                                      \
        const Py_ssize_t w = s->w;                                         \
        const T *p = (const T *)s->src + r0 * w;                           \
        char *d = s->dst + r0 * s->dstride;                                \
        const A bias = (A)s->bias;                                         \
        A acc = 0;                                                         \
        for (Py_ssize_t r = r0; r < r1; r++, p += w, d += s->dstride) {    \
            O *o = (O *)d;                                                 \
            for (Py_ssize_t j = 0; j < w; j++) {                           \
                A b = (A)p[j] + bias;                                      \
                acc |= b;                                                  \
                o[j] = (O)b;                                               \
            }                                                              \
        }                                                                  \
        s->acc |= acc;                                                     \
    }

/* two biased values a byte, the even one in the low nibble */
#define DEF_NIBBLE(NAME, T, A)                                             \
    static void NAME(step_t *s, Py_ssize_t r0, Py_ssize_t r1)              \
    {                                                                      \
        const Py_ssize_t w = s->w, h = w / 2;                              \
        const T *p = (const T *)s->src + r0 * w;                           \
        char *d = s->dst + r0 * s->dstride;                                \
        const A bias = (A)s->bias;                                         \
        A acc = 0;                                                         \
        for (Py_ssize_t r = r0; r < r1; r++, p += w, d += s->dstride) {    \
            uint8_t *o = (uint8_t *)d;                                     \
            for (Py_ssize_t j = 0; j < h; j++) {                           \
                A a = (A)p[2 * j] + bias, b = (A)p[2 * j + 1] + bias;      \
                acc |= a | b;                                              \
                o[j] = (uint8_t)(a | (b << 4));                            \
            }                                                              \
        }                                                                  \
        s->acc |= acc;                                                     \
    }

static inline uint8_t
hash_index(const step_t *s, int64_t v)
{
    uint64_t h = ((uint64_t)v * UINT64_C(0x9E3779B97F4A7C15))
                 >> (64 - HASH_BITS);
    while (s->hvals[h] != ABSENT && s->hkeys[h] != v)
        h = (h + 1) & ((1u << HASH_BITS) - 1);
    return s->hvals[h];
}

/* The index of each value in the corpus dictionary, INDEX(v) one of the
 * two lookups.  The byte table has one entry past its span, ABSENT, where
 * every value outside it lands. */
#define DEF_DICT(NAME, T, INDEX)                                           \
    static void NAME(step_t *s, Py_ssize_t r0, Py_ssize_t r1)              \
    {                                                                      \
        const Py_ssize_t w = s->w;                                         \
        const T *p = (const T *)s->src + r0 * w;                           \
        char *d = s->dst + r0 * s->dstride;                                \
        const uint8_t *tab = s->dense;                                     \
        const uint64_t base = (uint64_t)s->base, span = s->span;           \
        uint8_t top = 0;                                                   \
        (void)tab, (void)base, (void)span;                                 \
        for (Py_ssize_t r = r0; r < r1; r++, p += w, d += s->dstride) {    \
            uint8_t *o = (uint8_t *)d;                                     \
            for (Py_ssize_t j = 0; j < w; j++) {                           \
                uint8_t ix = INDEX(p[j]);                                  \
                top = ix > top ? ix : top;                                 \
                o[j] = ix;                                                 \
            }                                                              \
        }                                                                  \
        s->bad |= top == ABSENT;                                           \
    }
#define DENSE_INDEX(v)                                                     \
    tab[(uint64_t)(int64_t)(v) - base < span                               \
            ? (uint64_t)(int64_t)(v) - base : span]
#define HASH_INDEX(v) hash_index(s, (int64_t)(v))

DEF_CHECK(check_i4, int32_t)
DEF_CHECK(check_i8, int64_t)
DEF_CHECK(check_i1, int8_t)
DEF_CHECK(check_u1, uint8_t)
DEF_BIAS(bias_i4_u1, int32_t, uint32_t, uint8_t)
DEF_BIAS(bias_i4_u2, int32_t, uint32_t, uint16_t)
DEF_BIAS(bias_i8_u1, int64_t, uint64_t, uint8_t)
DEF_BIAS(bias_i8_u2, int64_t, uint64_t, uint16_t)
DEF_BIAS(bias_i1_u1, int8_t, uint32_t, uint8_t)
DEF_BIAS(bias_i1_u2, int8_t, uint32_t, uint16_t)
DEF_NIBBLE(nibble_i4, int32_t, uint32_t)
DEF_NIBBLE(nibble_i8, int64_t, uint64_t)
DEF_NIBBLE(nibble_i1, int8_t, uint32_t)
DEF_DICT(dense_i4, int32_t, DENSE_INDEX)
DEF_DICT(dense_i8, int64_t, DENSE_INDEX)
DEF_DICT(hash_i4, int32_t, HASH_INDEX)
DEF_DICT(hash_i8, int64_t, HASH_INDEX)

/* A float is inside the stored type once biased and integral, or the
 * step fails; what is converted is then a small whole number (a NaN is
 * inside no range). */
static inline int32_t
whole(const step_t *s, float v, int *bad)
{
    int ok = (v >= s->flo) & (v <= s->fhi);
    float c = ok ? v : 0.0f;
    int32_t iv = (int32_t)c;
    *bad |= !ok | ((float)iv != c);
    return iv;
}

static void
check_f4(step_t *s, Py_ssize_t r0, Py_ssize_t r1)
{
    const float *p = (const float *)s->src + r0 * s->w;
    const Py_ssize_t m = (r1 - r0) * s->w;
    int bad = 0;
    for (Py_ssize_t i = 0; i < m; i++)
        bad |= !((p[i] >= s->flo) & (p[i] <= s->fhi));
    s->bad |= bad;
}

#define DEF_BIAS_F4(NAME, O)                                               \
    static void NAME(step_t *s, Py_ssize_t r0, Py_ssize_t r1)              \
    {                                                                      \
        const Py_ssize_t w = s->w;                                         \
        const float *p = (const float *)s->src + r0 * w;                   \
        char *d = s->dst + r0 * s->dstride;                                \
        const int32_t bias = (int32_t)s->bias;                             \
        int bad = 0;                                                       \
        for (Py_ssize_t r = r0; r < r1; r++, p += w, d += s->dstride) {    \
            O *o = (O *)d;                                                 \
            for (Py_ssize_t j = 0; j < w; j++)                             \
                o[j] = (O)(whole(s, p[j], &bad) + bias);                   \
        }                                                                  \
        s->bad |= bad;                                                     \
    }

DEF_BIAS_F4(bias_f4_u1, uint8_t)
DEF_BIAS_F4(bias_f4_u2, uint16_t)

static void
nibble_f4(step_t *s, Py_ssize_t r0, Py_ssize_t r1)
{
    const Py_ssize_t w = s->w, h = w / 2;
    const float *p = (const float *)s->src + r0 * w;
    char *d = s->dst + r0 * s->dstride;
    const int32_t bias = (int32_t)s->bias;
    int bad = 0;
    for (Py_ssize_t r = r0; r < r1; r++, p += w, d += s->dstride) {
        uint8_t *o = (uint8_t *)d;
        for (Py_ssize_t j = 0; j < h; j++) {
            uint8_t a = (uint8_t)(whole(s, p[2 * j], &bad) + bias);
            uint8_t b = (uint8_t)(whole(s, p[2 * j + 1], &bad) + bias);
            o[j] = (uint8_t)(a | (uint8_t)(b << 4));
        }
    }
    s->bad |= bad;
}

static void
copy_rows(step_t *s, Py_ssize_t r0, Py_ssize_t r1)
{
    const char *p = s->src + r0 * s->row_b;
    char *d = s->dst + r0 * s->dstride;
    if (s->dstride == s->row_b) {
        memcpy(d, p, (size_t)((r1 - r0) * s->row_b));
        return;
    }
    for (Py_ssize_t r = r0; r < r1; r++, p += s->row_b, d += s->dstride)
        memcpy(d, p, (size_t)s->row_b);
}

/* kernels[op][source kind][stored item size - 1]; NULL: no such step */
static const kernel_t kernels[N_OPS][K_OTHER][2] = {
    [OP_CHECK] = {[K_I4] = {check_i4}, [K_I8] = {check_i8},
                  [K_I1] = {check_i1}, [K_U1] = {check_u1},
                  [K_F4] = {check_f4}},
    [OP_BIAS] = {[K_I4] = {bias_i4_u1, bias_i4_u2},
                 [K_I8] = {bias_i8_u1, bias_i8_u2},
                 [K_I1] = {bias_i1_u1, bias_i1_u2},
                 [K_F4] = {bias_f4_u1, bias_f4_u2}},
    [OP_NIBBLE] = {[K_I4] = {nibble_i4, NULL}, [K_I8] = {nibble_i8, NULL},
                   [K_I1] = {nibble_i1, NULL}, [K_F4] = {nibble_f4, NULL}},
    /* a dictionary stores one byte: the second slot is the hashed form */
    [OP_DICT] = {[K_I4] = {dense_i4, hash_i4}, [K_I8] = {dense_i8, hash_i8}},
};

static int
source_kind(const Py_buffer *v)
{
    const char *f = v->format ? v->format : "B";
    while (*f == '<' || *f == '=' || *f == '@' || *f == '|')
        f++;
    if (f[0] == '\0' || f[1] != '\0')
        return K_OTHER;
    switch (f[0]) {
    case 'i':
        return v->itemsize == 4 ? K_I4 : K_OTHER;
    case 'l':
    case 'q':
        return v->itemsize == 8 ? K_I8 : (v->itemsize == 4 ? K_I4 : K_OTHER);
    case 'b':
        return K_I1;
    case 'B':
    case '?':
        return K_U1;
    case 'f':
        return v->itemsize == 4 ? K_F4 : K_OTHER;
    default:
        return K_OTHER;
    }
}

/* the lookup of a DICT step from its sorted dictionary; -1: no memory */
static int
build_dict(step_t *s, const int64_t *dv, Py_ssize_t ndv)
{
    uint64_t span = (uint64_t)dv[ndv - 1] - (uint64_t)dv[0] + 1;
    if (span != 0 && span <= DENSE_SPAN) {
        s->dense = malloc((size_t)span + 1);
        if (s->dense == NULL)
            return -1;
        memset(s->dense, ABSENT, (size_t)span + 1);
        for (Py_ssize_t i = 0; i < ndv; i++)
            s->dense[(uint64_t)dv[i] - (uint64_t)dv[0]] = (uint8_t)i;
        s->base = dv[0];
        s->span = span;
        return 0;
    }
    const size_t slots = (size_t)1 << HASH_BITS;
    s->hkeys = malloc(slots * sizeof(int64_t));
    s->hvals = malloc(slots);
    if (s->hkeys == NULL || s->hvals == NULL)
        return -1;
    memset(s->hvals, ABSENT, slots);
    for (Py_ssize_t i = 0; i < ndv; i++) {
        uint64_t h = ((uint64_t)dv[i] * UINT64_C(0x9E3779B97F4A7C15))
                     >> (64 - HASH_BITS);
        while (s->hvals[h] != ABSENT)
            h = (h + 1) & (slots - 1);
        s->hkeys[h] = dv[i];
        s->hvals[h] = (uint8_t)i;
    }
    return 0;
}

/* One step from its tuple (op, src, dst, off, arg): arg is the constant
 * (CHECK), the bias (BIAS, NIBBLE) or the sorted dictionary (DICT).  The
 * source's buffer goes into sv[0] and the destination's into sv[1], and the
 * caller holds both until the last block is written; the dictionary's is
 * let go here, its table being a copy.  -1 with an error set. */
static int
parse_step(PyObject *item, Py_ssize_t n, step_t *s, Py_buffer *sv)
{
    int op;
    PyObject *src, *dst, *arg;
    Py_ssize_t off;
    if (!PyTuple_Check(item)
        || !PyArg_ParseTuple(item, "iOOnO", &op, &src, &dst, &off, &arg))
        return -1;
    if (op < 0 || op >= N_OPS) {
        PyErr_Format(PyExc_ValueError, "unknown step %d", op);
        return -1;
    }
    if (PyObject_GetBuffer(src, sv, PyBUF_C_CONTIGUOUS | PyBUF_FORMAT) < 0)
        return -1;
    int kind = source_kind(sv);
    Py_ssize_t items = sv->itemsize > 0 ? sv->len / sv->itemsize : 0;
    if (items % n != 0) {
        PyErr_SetString(PyExc_ValueError, "a column that is not [n, w]");
        return -1;
    }
    s->src = sv->buf;
    s->w = items / n;
    s->row_b = s->w * sv->itemsize;
    s->ilo = s->imn = INT64_MIN;
    s->ihi = s->imx = INT64_MAX;
    s->cap = UINT64_MAX;
    Py_ssize_t dsz = 1;
    if (op != OP_CHECK) {
        Py_buffer *dv = &sv[1];
        if (PyObject_GetBuffer(dst, dv, PyBUF_C_CONTIGUOUS | PyBUF_WRITABLE)
                < 0)
            return -1;
        dsz = dv->itemsize;
        Py_ssize_t stored = op == OP_NIBBLE ? s->w / 2 : s->w;
        if (dv->ndim != 2 || dv->shape[0] != n || off < 0
            || off + stored > dv->shape[1] || (op == OP_NIBBLE && s->w % 2)
            || (op == OP_COPY ? dsz != sv->itemsize
                              : dsz > (op == OP_BIAS ? 2 : 1))) {
            PyErr_SetString(PyExc_ValueError,
                            "a step that does not fit its buffer");
            return -1;
        }
        s->dst = (char *)dv->buf + off * dsz;
        s->dstride = dv->shape[1] * dsz;
    }
    s->fn = op == OP_COPY ? copy_rows
            : kind == K_OTHER ? NULL : kernels[op][kind][dsz - 1];
    if (s->fn == NULL) {
        PyErr_Format(PyExc_TypeError,
                     "step %d cannot read a column of format %s", op,
                     sv->format ? sv->format : "B");
        return -1;
    }
    if (op == OP_CHECK && kind == K_F4) {
        double c = PyFloat_AsDouble(arg);
        if (PyErr_Occurred())
            return -1;
        s->flo = s->fhi = (float)c;
    } else if (op == OP_CHECK) {
        PyObject *c = PyNumber_Long(arg); /* 3.0 for an integer column */
        if (c == NULL)
            return -1;
        s->ilo = s->ihi = PyLong_AsLongLong(c);
        Py_DECREF(c);
        s->imn = INT64_MAX;
        s->imx = INT64_MIN;
        if (PyErr_Occurred())
            return -1;
    } else if (op == OP_BIAS || op == OP_NIBBLE) {
        s->bias = PyLong_AsLongLong(arg);
        if (PyErr_Occurred())
            return -1;
        s->cap = op == OP_NIBBLE ? 0xF : dsz == 1 ? 0xFF : 0xFFFF;
        s->flo = (float)-s->bias;
        s->fhi = (float)((int64_t)s->cap - s->bias);
    } else if (op == OP_DICT) {
        Py_buffer tv;
        if (PyObject_GetBuffer(arg, &tv, PyBUF_C_CONTIGUOUS | PyBUF_FORMAT)
                < 0)
            return -1;
        Py_ssize_t ndv = tv.len / 8;
        int rc = -2;
        if (source_kind(&tv) == K_I8 && ndv > 0 && ndv < ABSENT)
            rc = s->w > 0 ? build_dict(s, tv.buf, ndv) : 0;
        PyBuffer_Release(&tv);
        if (rc == 0 && s->dense == NULL)
            s->fn = kernels[op][kind][1];
        if (rc == -1)
            PyErr_NoMemory();
        else if (rc == -2)
            PyErr_SetString(PyExc_ValueError,
                            "a dictionary is 1 to 254 sorted int64");
        if (rc < 0)
            return -1;
    }
    return 0;
}

static _Thread_local double released_s;

static double
thread_cpu_s(void)
{
    struct timespec ts;
    if (clock_gettime(CLOCK_THREAD_CPUTIME_ID, &ts) != 0)
        return 0.0;
    return (double)ts.tv_sec + (double)ts.tv_nsec * 1e-9;
}

/* released_cpu() -> CPU seconds the calling thread has spent in pack()
 * with the GIL released, since it first called in */
static PyObject *
released_cpu(PyObject *self, PyObject *noargs)
{
    (void)self;
    (void)noargs;
    return PyFloat_FromDouble(released_s);
}

/* pack(steps, n) -> the indices of the steps that failed their check */
static PyObject *
pack(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *steps;
    Py_ssize_t n;
    if (!PyArg_ParseTuple(args, "O!n", &PyList_Type, &steps, &n))
        return NULL;
    Py_ssize_t m = PyList_GET_SIZE(steps);
    if (n <= 0 || m == 0)
        return PyList_New(0);
    step_t *plan = calloc((size_t)m, sizeof(step_t));
    Py_buffer *views = calloc((size_t)m * 2, sizeof(Py_buffer));
    PyObject *out = NULL;
    if (plan == NULL || views == NULL) {
        PyErr_NoMemory();
        goto done;
    }
    for (Py_ssize_t i = 0; i < m; i++)
        if (parse_step(PyList_GET_ITEM(steps, i), n, &plan[i], &views[2 * i])
                < 0)
            goto done;
    Py_BEGIN_ALLOW_THREADS
    double c0 = thread_cpu_s();
    for (Py_ssize_t r0 = 0; r0 < n; r0 += BLOCK_ROWS) {
        Py_ssize_t r1 = r0 + BLOCK_ROWS < n ? r0 + BLOCK_ROWS : n;
        for (Py_ssize_t i = 0; i < m; i++)
            if (plan[i].w > 0)
                plan[i].fn(&plan[i], r0, r1);
    }
    released_s += thread_cpu_s() - c0;
    Py_END_ALLOW_THREADS
    out = PyList_New(0);
    for (Py_ssize_t i = 0; out != NULL && i < m; i++) {
        step_t *s = &plan[i];
        if (s->w == 0 || !(s->bad || s->acc > s->cap || s->imn < s->ilo
                           || s->imx > s->ihi))
            continue;
        PyObject *ix = PyLong_FromSsize_t(i);
        if (ix == NULL || PyList_Append(out, ix) < 0)
            Py_CLEAR(out);
        Py_XDECREF(ix);
    }
done:
    for (Py_ssize_t i = 0; views != NULL && i < 2 * m; i++)
        if (views[i].obj != NULL)
            PyBuffer_Release(&views[i]);
    if (plan != NULL)
        for (Py_ssize_t i = 0; i < m; i++) {
            free(plan[i].dense);
            free(plan[i].hkeys);
            free(plan[i].hvals);
        }
    free(plan);
    free(views);
    return out;
}

static PyMethodDef methods[] = {
    {"pack", pack, METH_VARARGS,
     "Run a chunk's wire-pack steps; the indices of those that failed."},
    {"released_cpu", released_cpu, METH_NOARGS,
     "CPU seconds of the calling thread inside pack() with the GIL "
     "released."},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "gtpu_wirepack", NULL, -1, methods,
    NULL, NULL, NULL, NULL,
};

PyMODINIT_FUNC
PyInit_gtpu_wirepack(void)
{
    PyObject *mod = PyModule_Create(&moduledef);
    if (mod == NULL)
        return NULL;
    static const char *names[N_OPS] = {"CHECK", "COPY", "BIAS", "NIBBLE",
                                       "DICT"};
    for (int i = 0; i < N_OPS; i++)
        if (PyModule_AddIntConstant(mod, names[i], i) < 0) {
            Py_DECREF(mod);
            return NULL;
        }
    return mod;
}
