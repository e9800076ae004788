/* gtpu_flattenjson: threaded, GIL-released JSON -> columnar flattener.
 *
 * The dict-walking columnizer (flattenmod.c) can never release the GIL:
 * it touches PyObjects on every step, which caps a host at ~65k objects/s
 * (one core) -- below the 100k reviews/s/chip target of BASELINE.md even
 * with an infinitely fast device.  This module moves the host->device
 * boundary to raw JSON bytes: each batch item is parsed and columnized
 * entirely in C with the GIL released, sharded over a pthread pool.
 *
 * Interning is three-phase so ids stay consistent with the shared Python
 * Vocab (ops/flatten.py) without a lock on the hot path:
 *   1. (no GIL, threads) parse + columnize; strings intern into
 *      per-thread tables, sid cells hold thread-local ids.
 *   2. (GIL) per-thread tables merge into the Python vocab in
 *      deterministic (thread, first-seen) order -> local->global maps.
 *   3. (no GIL, threads) sid arrays remap in-place per row range.
 *
 * The output arrays are made under the GIL (PyArray_EMPTY: no page of
 * them touched there) at their final width, which is the batch's own
 * maximum or the caller's floor, whichever is wider; their prefill (0,
 * and -1 or -2 for the sid and index arrays) is written by the workers,
 * each over its own rows, inside phases 1 and 2 (FillList).
 *
 * Semantics mirror ops/flatten.py exactly (differential-tested in
 * tests/test_native_flatten.py) -- the Python flattener remains the
 * oracle.  Reference anchor for the loop this replaces: the audit
 * spill-review loop, /root/reference/pkg/audit/manager.go:686-774.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <math.h>
#include <pthread.h>
#include <stdint.h>
#include <string.h>
#include <time.h>

#define NPY_NO_DEPRECATED_API NPY_1_7_API_VERSION
#include <numpy/arrayobject.h>

/* value-kind tags (must match ops/flatten.py) */
enum { K_ABSENT = 0, K_FALSE = 1, K_TRUE = 2, K_NUM = 3, K_STR = 4,
       K_OTHER = 5, K_NULL = 6, K_MAP = 7 };

/* SWAR (SIMD-within-a-register) byte scanning: find quote/backslash/
 * whitespace bytes 8 at a time with the classic haszero bit trick.
 * Little-endian GCC/Clang hosts only; everything falls back to the
 * scalar loops elsewhere. */
#if defined(__GNUC__) && defined(__BYTE_ORDER__) && \
    __BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__
#define GTPU_SWAR 1
#define SWAR_ONES 0x0101010101010101ULL
#define SWAR_HIGH 0x8080808080808080ULL

static inline uint64_t
swar_eq(uint64_t w, uint64_t b)
{
    uint64_t x = w ^ (SWAR_ONES * b);
    return (x - SWAR_ONES) & ~x & SWAR_HIGH;
}
#endif

/* ---------------- arena ---------------- */

typedef struct ArenaBlock {
    struct ArenaBlock *next;
    size_t used, cap;
    char data[];
} ArenaBlock;

typedef struct {
    ArenaBlock *head;
} Arena;

static void *
arena_alloc(Arena *a, size_t sz)
{
    sz = (sz + 15) & ~(size_t)15;
    if (a->head == NULL || a->head->used + sz > a->head->cap) {
        size_t cap = 1 << 20;
        if (cap < sz)
            cap = sz;
        ArenaBlock *b = (ArenaBlock *)malloc(sizeof(ArenaBlock) + cap);
        if (b == NULL)
            return NULL;
        b->next = a->head;
        b->used = 0;
        b->cap = cap;
        a->head = b;
    }
    void *p = a->head->data + a->head->used;
    a->head->used += sz;
    return p;
}

static void
arena_free(Arena *a)
{
    ArenaBlock *b = a->head;
    while (b) {
        ArenaBlock *n = b->next;
        free(b);
        b = n;
    }
    a->head = NULL;
}

/* ---------------- per-thread string interner ---------------- */

typedef struct {
    const char **strs;   /* local id -> ptr */
    uint32_t *lens;      /* local id -> len */
    uint32_t count, scap;
    int32_t *tab;        /* open addressing; value = local id + 1 */
    uint32_t *tabhash;
    uint32_t cap;        /* power of two */
} Intern;

static uint32_t
fnv1a(const char *s, uint32_t n)
{
    uint32_t h = 2166136261u;
    for (uint32_t i = 0; i < n; i++) {
        h ^= (unsigned char)s[i];
        h *= 16777619u;
    }
    return h;
}

static int
intern_init(Intern *it)
{
    it->cap = 1 << 12;
    it->scap = 1 << 10;
    it->count = 0;
    it->strs = (const char **)malloc(it->scap * sizeof(char *));
    it->lens = (uint32_t *)malloc(it->scap * sizeof(uint32_t));
    it->tab = (int32_t *)calloc(it->cap, sizeof(int32_t));
    it->tabhash = (uint32_t *)malloc(it->cap * sizeof(uint32_t));
    return (it->strs && it->lens && it->tab && it->tabhash) ? 0 : -1;
}

static void
intern_destroy(Intern *it)
{
    free(it->strs); free(it->lens); free(it->tab); free(it->tabhash);
}

static int
intern_grow(Intern *it)
{
    uint32_t ncap = it->cap << 1;
    int32_t *ntab = (int32_t *)calloc(ncap, sizeof(int32_t));
    uint32_t *nhash = (uint32_t *)malloc(ncap * sizeof(uint32_t));
    if (!ntab || !nhash) {
        free(ntab); free(nhash);
        return -1;
    }
    for (uint32_t i = 0; i < it->cap; i++) {
        if (it->tab[i]) {
            uint32_t h = it->tabhash[i];
            uint32_t j = h & (ncap - 1);
            while (ntab[j])
                j = (j + 1) & (ncap - 1);
            ntab[j] = it->tab[i];
            nhash[j] = h;
        }
    }
    free(it->tab); free(it->tabhash);
    it->tab = ntab; it->tabhash = nhash; it->cap = ncap;
    return 0;
}

/* returns local id, or -1 on OOM */
static int32_t
intern_get(Intern *it, const char *s, uint32_t n)
{
    uint32_t h = fnv1a(s, n);
    uint32_t j = h & (it->cap - 1);
    while (it->tab[j]) {
        if (it->tabhash[j] == h) {
            int32_t id = it->tab[j] - 1;
            if (it->lens[id] == n && memcmp(it->strs[id], s, n) == 0)
                return id;
        }
        j = (j + 1) & (it->cap - 1);
    }
    if (it->count == it->scap) {
        it->scap <<= 1;
        const char **ns = (const char **)realloc(
            (void *)it->strs, it->scap * sizeof(char *));
        uint32_t *nl = (uint32_t *)realloc(it->lens,
                                           it->scap * sizeof(uint32_t));
        if (!ns || !nl) {
            if (ns) it->strs = ns;
            if (nl) it->lens = nl;
            return -1;
        }
        it->strs = ns; it->lens = nl;
    }
    int32_t id = (int32_t)it->count++;
    it->strs[id] = s;
    it->lens[id] = n;
    it->tab[j] = id + 1;
    it->tabhash[j] = h;
    if (it->count * 2 > it->cap && intern_grow(it) < 0)
        return -1;
    return id;
}

/* probe without inserting: id or -1 */
static int32_t
intern_lookup(const Intern *it, const char *s, uint32_t n)
{
    uint32_t h = fnv1a(s, n);
    uint32_t j = h & (it->cap - 1);
    while (it->tab[j]) {
        if (it->tabhash[j] == h) {
            int32_t id = it->tab[j] - 1;
            if (it->lens[id] == n && memcmp(it->strs[id], s, n) == 0)
                return id;
        }
        j = (j + 1) & (it->cap - 1);
    }
    return -1;
}

/* reset for reuse: entries dropped, allocations kept */
static void
intern_reset(Intern *it)
{
    it->count = 0;
    memset(it->tab, 0, it->cap * sizeof(int32_t));
}

/* ---------------- persistent global vocab mirror ----------------
 *
 * The batch merge used to round-trip EVERY thread-locally interned
 * string through the Python vocab dict (PyUnicode_DecodeUTF8 +
 * PyDict_GetItem per string per batch) — over a chunked sweep the same
 * ~36k-string vocabulary re-pays that cost on every chunk.  The mirror
 * is a C-side positive cache of the Python vocab: entry i holds the
 * UTF-8 bytes of to_str[i] (an owned reference keeps the unicode
 * object's cached UTF-8 buffer alive), so merge hits resolve with one
 * C hash probe and only genuinely-new strings touch Python objects.
 *
 * All mutation happens with the GIL held.  Correctness does not depend
 * on the mirror being complete: it only ever holds verified
 * (bytes -> position-in-to_str) pairs, so a hit is always right and a
 * miss falls back to the exact dict path.  Vocab identity changes
 * (a different Vocab object) reset it; a to_str that shrank or carries
 * duplicates disables it until the next identity change. */

typedef struct {
    PyObject *to_id;    /* identity markers only (borrowed, never used) */
    PyObject *to_str;
    PyObject **objs;    /* owned refs: entry i == to_str[i] */
    Py_ssize_t count, cap;
    Intern table;       /* bytes -> mirrored position */
    int inited;
    int disabled;       /* duplicate/undecodable vocab entry seen */
} VocabMirror;

static VocabMirror g_vm;

/* append one vocab string; 0 ok, 1 skip (dup / no utf8), -1 oom */
static int
vm_push(PyObject *s)
{
    Py_ssize_t len;
    const char *u = PyUnicode_AsUTF8AndSize(s, &len);
    if (u == NULL) {
        PyErr_Clear();
        return 1;
    }
    if (g_vm.count == g_vm.cap) {
        Py_ssize_t ncap = g_vm.cap * 2;
        PyObject **no = (PyObject **)realloc(
            (void *)g_vm.objs, (size_t)ncap * sizeof(PyObject *));
        if (no == NULL)
            return -1;
        g_vm.objs = no;
        g_vm.cap = ncap;
    }
    int32_t id = intern_get(&g_vm.table, u, (uint32_t)len);
    if (id < 0)
        return -1;
    if (id != (int32_t)g_vm.count)
        return 1; /* duplicate string: table unchanged (probe hit) */
    Py_INCREF(s);
    g_vm.objs[g_vm.count++] = s;
    return 0;
}

static int
vm_reset(void)
{
    for (Py_ssize_t i = 0; i < g_vm.count; i++)
        Py_DECREF(g_vm.objs[i]);
    g_vm.count = 0;
    g_vm.disabled = 0;
    if (!g_vm.inited) {
        g_vm.cap = 1024;
        g_vm.objs = (PyObject **)malloc((size_t)g_vm.cap *
                                        sizeof(PyObject *));
        if (g_vm.objs == NULL || intern_init(&g_vm.table) < 0)
            return -1;
        g_vm.inited = 1;
    } else {
        intern_reset(&g_vm.table);
    }
    return 0;
}

/* sync the mirror up to len(to_str); 0 usable, 1 disabled, -1 oom */
static int
vm_sync(PyObject *to_id, PyObject *to_str)
{
    if (!g_vm.inited || g_vm.to_id != to_id || g_vm.to_str != to_str ||
        g_vm.count > PyList_GET_SIZE(to_str)) {
        if (vm_reset() < 0)
            return -1;
        g_vm.to_id = to_id;
        g_vm.to_str = to_str;
    }
    if (g_vm.disabled)
        return 1;
    Py_ssize_t n = PyList_GET_SIZE(to_str);
    for (Py_ssize_t i = g_vm.count; i < n; i++) {
        int r = vm_push(PyList_GET_ITEM(to_str, i));
        if (r < 0)
            return -1;
        if (r) {
            g_vm.disabled = 1;
            return 1;
        }
    }
    return 0;
}

/* ---------------- JSON DOM + parser ---------------- */

enum { JT_NULL, JT_FALSE, JT_TRUE, JT_NUM, JT_STR, JT_ARR, JT_OBJ };

typedef struct JNode JNode;
struct JNode {
    uint8_t type;
    uint32_t n; /* children count (arr/obj) or byte length (str) */
    union {
        double num;
        const char *str;
        JNode **items;                 /* JT_ARR */
        struct {
            const char **keys;
            uint32_t *klens;
            JNode **vals;
        } obj;                         /* JT_OBJ */
    } u;
};

typedef struct {
    const char *p, *end;
    Arena *arena;
    /* scratch stacks for building child arrays */
    JNode **nstack;
    const char **kstack;
    uint32_t *lstack;
    size_t stop, scap;
    int err;
} Parser;

static int
pstack_reserve(Parser *ps, size_t need)
{
    if (ps->stop + need <= ps->scap)
        return 0;
    size_t ncap = ps->scap ? ps->scap * 2 : 256;
    while (ncap < ps->stop + need)
        ncap *= 2;
    JNode **nn = (JNode **)realloc((void *)ps->nstack,
                                   ncap * sizeof(JNode *));
    const char **nk = (const char **)realloc((void *)ps->kstack,
                                             ncap * sizeof(char *));
    uint32_t *nl = (uint32_t *)realloc(ps->lstack, ncap * sizeof(uint32_t));
    if (!nn || !nk || !nl) {
        if (nn) ps->nstack = nn;
        if (nk) ps->kstack = nk;
        if (nl) ps->lstack = nl;
        return -1;
    }
    ps->nstack = nn; ps->kstack = nk; ps->lstack = nl; ps->scap = ncap;
    return 0;
}

static void
skip_ws(Parser *ps)
{
    const char *p = ps->p;
    const char *end = ps->end;
    /* minified K8s serializations: the first byte almost always breaks
     * straight out; the SWAR run-skip only engages after a whitespace
     * byte was actually seen (pretty-printed docs: indentation runs) */
    while (p < end) {
        char c = *p;
        if (c != ' ' && c != '\t' && c != '\n' && c != '\r')
            break;
        p++;
#ifdef GTPU_SWAR
        while (p + 8 <= end) {
            uint64_t w, ws;
            memcpy(&w, p, 8);
            ws = swar_eq(w, ' ') | swar_eq(w, '\n') |
                 swar_eq(w, '\t') | swar_eq(w, '\r');
            if (ws != SWAR_HIGH)
                break;
            p += 8;
        }
#endif
    }
    ps->p = p;
}

static JNode *
jnode_new(Parser *ps, uint8_t type)
{
    JNode *n = (JNode *)arena_alloc(ps->arena, sizeof(JNode));
    if (n == NULL) {
        ps->err = 1;
        return NULL;
    }
    n->type = type;
    n->n = 0;
    return n;
}

/* UTF-8 encode cp into out; returns bytes written */
static int
utf8_put(char *out, uint32_t cp)
{
    if (cp < 0x80) {
        out[0] = (char)cp;
        return 1;
    } else if (cp < 0x800) {
        out[0] = (char)(0xC0 | (cp >> 6));
        out[1] = (char)(0x80 | (cp & 0x3F));
        return 2;
    } else if (cp < 0x10000) {
        out[0] = (char)(0xE0 | (cp >> 12));
        out[1] = (char)(0x80 | ((cp >> 6) & 0x3F));
        out[2] = (char)(0x80 | (cp & 0x3F));
        return 3;
    }
    out[0] = (char)(0xF0 | (cp >> 18));
    out[1] = (char)(0x80 | ((cp >> 12) & 0x3F));
    out[2] = (char)(0x80 | ((cp >> 6) & 0x3F));
    out[3] = (char)(0x80 | (cp & 0x3F));
    return 4;
}

static int
hex4(const char *p, uint32_t *out)
{
    uint32_t v = 0;
    for (int i = 0; i < 4; i++) {
        char c = p[i];
        v <<= 4;
        if (c >= '0' && c <= '9') v |= (uint32_t)(c - '0');
        else if (c >= 'a' && c <= 'f') v |= (uint32_t)(c - 'a' + 10);
        else if (c >= 'A' && c <= 'F') v |= (uint32_t)(c - 'A' + 10);
        else return -1;
    }
    *out = v;
    return 0;
}

/* parse a JSON string (after the opening quote); returns 0 ok.
 * *sout and *nout point into the input (no escapes) or an arena copy. */
static int
parse_string(Parser *ps, const char **sout, uint32_t *nout)
{
    const char *p = ps->p;
    const char *start = p;
#ifdef GTPU_SWAR
    while (p + 8 <= ps->end) {
        uint64_t w, hit;
        memcpy(&w, p, 8);
        hit = swar_eq(w, '"') | swar_eq(w, '\\');
        if (hit) {
            p += __builtin_ctzll(hit) >> 3;
            break;
        }
        p += 8;
    }
#endif
    while (p < ps->end && *p != '"' && *p != '\\')
        p++;
    if (p >= ps->end)
        return -1;
    if (*p == '"') { /* fast path: no escapes */
        *sout = start;
        *nout = (uint32_t)(p - start);
        ps->p = p + 1;
        return 0;
    }
    /* slow path: decode escapes into arena buffer (<= raw length) */
    size_t maxlen = 0;
    {
        const char *q = p;
        int esc = 0;
        while (q < ps->end) {
            if (esc) esc = 0;
            else if (*q == '\\') esc = 1;
            else if (*q == '"') break;
            q++;
        }
        if (q >= ps->end)
            return -1;
        maxlen = (size_t)(q - start) + 4;
    }
    char *buf = (char *)arena_alloc(ps->arena, maxlen);
    if (buf == NULL)
        return -1;
    size_t o = (size_t)(p - start);
    memcpy(buf, start, o);
    while (p < ps->end && *p != '"') {
        if (*p != '\\') {
            buf[o++] = *p++;
            continue;
        }
        p++;
        if (p >= ps->end)
            return -1;
        char c = *p++;
        switch (c) {
        case '"': buf[o++] = '"'; break;
        case '\\': buf[o++] = '\\'; break;
        case '/': buf[o++] = '/'; break;
        case 'b': buf[o++] = '\b'; break;
        case 'f': buf[o++] = '\f'; break;
        case 'n': buf[o++] = '\n'; break;
        case 'r': buf[o++] = '\r'; break;
        case 't': buf[o++] = '\t'; break;
        case 'u': {
            uint32_t cp;
            if (p + 4 > ps->end || hex4(p, &cp) < 0)
                return -1;
            p += 4;
            if (cp >= 0xD800 && cp <= 0xDBFF && p + 6 <= ps->end &&
                p[0] == '\\' && p[1] == 'u') {
                uint32_t lo;
                if (hex4(p + 2, &lo) == 0 && lo >= 0xDC00 && lo <= 0xDFFF) {
                    cp = 0x10000 + ((cp - 0xD800) << 10) + (lo - 0xDC00);
                    p += 6;
                }
            }
            o += (size_t)utf8_put(buf + o, cp);
            break;
        }
        default:
            return -1;
        }
    }
    if (p >= ps->end)
        return -1;
    ps->p = p + 1;
    *sout = buf;
    *nout = (uint32_t)o;
    return 0;
}

static JNode *parse_value(Parser *ps, int depth);

static JNode *
parse_object(Parser *ps, int depth)
{
    /* collect keys/vals on the scratch stack, then copy to arena */
    size_t base = ps->stop;
    ps->p++; /* '{' */
    skip_ws(ps);
    if (ps->p < ps->end && *ps->p == '}') {
        ps->p++;
    } else {
        for (;;) {
            skip_ws(ps);
            if (ps->p >= ps->end || *ps->p != '"')
                return NULL;
            ps->p++;
            const char *ks;
            uint32_t kn;
            if (parse_string(ps, &ks, &kn) < 0)
                return NULL;
            skip_ws(ps);
            if (ps->p >= ps->end || *ps->p != ':')
                return NULL;
            ps->p++;
            JNode *v = parse_value(ps, depth + 1);
            if (v == NULL)
                return NULL;
            /* duplicate key: last wins (json.loads semantics) */
            int dup = 0;
            for (size_t i = base; i < ps->stop; i++) {
                if (ps->lstack[i] == kn &&
                    memcmp(ps->kstack[i], ks, kn) == 0) {
                    ps->nstack[i] = v;
                    dup = 1;
                    break;
                }
            }
            if (!dup) {
                if (pstack_reserve(ps, 1) < 0)
                    return NULL;
                ps->nstack[ps->stop] = v;
                ps->kstack[ps->stop] = ks;
                ps->lstack[ps->stop] = kn;
                ps->stop++;
            }
            skip_ws(ps);
            if (ps->p < ps->end && *ps->p == ',') {
                ps->p++;
                continue;
            }
            if (ps->p < ps->end && *ps->p == '}') {
                ps->p++;
                break;
            }
            return NULL;
        }
    }
    JNode *n = jnode_new(ps, JT_OBJ);
    if (n == NULL)
        return NULL;
    size_t cnt = ps->stop - base;
    n->n = (uint32_t)cnt;
    if (cnt) {
        n->u.obj.keys = (const char **)arena_alloc(ps->arena,
                                                   cnt * sizeof(char *));
        n->u.obj.klens = (uint32_t *)arena_alloc(ps->arena,
                                                 cnt * sizeof(uint32_t));
        n->u.obj.vals = (JNode **)arena_alloc(ps->arena,
                                              cnt * sizeof(JNode *));
        if (!n->u.obj.keys || !n->u.obj.klens || !n->u.obj.vals)
            return NULL;
        memcpy((void *)n->u.obj.keys, ps->kstack + base,
               cnt * sizeof(char *));
        memcpy(n->u.obj.klens, ps->lstack + base, cnt * sizeof(uint32_t));
        memcpy((void *)n->u.obj.vals, ps->nstack + base,
               cnt * sizeof(JNode *));
    }
    ps->stop = base;
    return n;
}

static JNode *
parse_array(Parser *ps, int depth)
{
    size_t base = ps->stop;
    ps->p++; /* '[' */
    skip_ws(ps);
    if (ps->p < ps->end && *ps->p == ']') {
        ps->p++;
    } else {
        for (;;) {
            JNode *v = parse_value(ps, depth + 1);
            if (v == NULL)
                return NULL;
            if (pstack_reserve(ps, 1) < 0)
                return NULL;
            ps->nstack[ps->stop] = v;
            ps->kstack[ps->stop] = NULL;
            ps->lstack[ps->stop] = 0;
            ps->stop++;
            skip_ws(ps);
            if (ps->p < ps->end && *ps->p == ',') {
                ps->p++;
                continue;
            }
            if (ps->p < ps->end && *ps->p == ']') {
                ps->p++;
                break;
            }
            return NULL;
        }
    }
    JNode *n = jnode_new(ps, JT_ARR);
    if (n == NULL)
        return NULL;
    size_t cnt = ps->stop - base;
    n->n = (uint32_t)cnt;
    if (cnt) {
        n->u.items = (JNode **)arena_alloc(ps->arena, cnt * sizeof(JNode *));
        if (n->u.items == NULL)
            return NULL;
        memcpy((void *)n->u.items, ps->nstack + base, cnt * sizeof(JNode *));
    }
    ps->stop = base;
    return n;
}

static JNode *
parse_value(Parser *ps, int depth)
{
    if (depth > 256)
        return NULL;
    skip_ws(ps);
    if (ps->p >= ps->end)
        return NULL;
    char c = *ps->p;
    if (c == '{')
        return parse_object(ps, depth);
    if (c == '[')
        return parse_array(ps, depth);
    if (c == '"') {
        ps->p++;
        JNode *n = jnode_new(ps, JT_STR);
        if (n == NULL)
            return NULL;
        if (parse_string(ps, &n->u.str, &n->n) < 0)
            return NULL;
        return n;
    }
    if (c == 't') {
        if (ps->end - ps->p < 4 || memcmp(ps->p, "true", 4) != 0)
            return NULL;
        ps->p += 4;
        return jnode_new(ps, JT_TRUE);
    }
    if (c == 'f') {
        if (ps->end - ps->p < 5 || memcmp(ps->p, "false", 5) != 0)
            return NULL;
        ps->p += 5;
        return jnode_new(ps, JT_FALSE);
    }
    if (c == 'n') {
        if (ps->end - ps->p < 4 || memcmp(ps->p, "null", 4) != 0)
            return NULL;
        ps->p += 4;
        return jnode_new(ps, JT_NULL);
    }
    /* number (json.loads also accepts NaN/Infinity/-Infinity) */
    if (c == 'N' && ps->end - ps->p >= 3 && memcmp(ps->p, "NaN", 3) == 0) {
        ps->p += 3;
        JNode *n = jnode_new(ps, JT_NUM);
        if (n) n->u.num = NAN;
        return n;
    }
    if (c == 'I' && ps->end - ps->p >= 8 &&
        memcmp(ps->p, "Infinity", 8) == 0) {
        ps->p += 8;
        JNode *n = jnode_new(ps, JT_NUM);
        if (n) n->u.num = HUGE_VAL;
        return n;
    }
    if (c == '-' && ps->end - ps->p >= 9 &&
        memcmp(ps->p, "-Infinity", 9) == 0) {
        ps->p += 9;
        JNode *n = jnode_new(ps, JT_NUM);
        if (n) n->u.num = -HUGE_VAL;
        return n;
    }
    if (c == '-' || (c >= '0' && c <= '9')) {
        /* fast path for short decimal integers (ports, counts, replica
         * numbers dominate K8s docs): <= 15 digits are exact in a double
         * and need none of strtod's locale/rounding machinery */
        const char *q = ps->p;
        if (*q == '-')
            q++;
        const char *d0 = q;
        uint64_t v = 0;
        while (q < ps->end && *q >= '0' && *q <= '9' && q - d0 < 16) {
            v = v * 10 + (uint64_t)(*q - '0');
            q++;
        }
        if (q > d0 && q - d0 <= 15 &&
            (q >= ps->end || (*q != '.' && *q != 'e' && *q != 'E'))) {
            ps->p = q;
            JNode *n = jnode_new(ps, JT_NUM);
            if (n) n->u.num = (c == '-') ? -(double)v : (double)v;
            return n;
        }
        char *endp = NULL;
        double d = strtod(ps->p, &endp);
        if (endp == ps->p)
            return NULL;
        ps->p = endp;
        JNode *n = jnode_new(ps, JT_NUM);
        if (n) n->u.num = d;
        return n;
    }
    return NULL;
}

/* parse one document; NULL on error.  Trailing garbage is an error
 * (json.loads semantics). */
static JNode *
parse_doc(Parser *ps, const char *buf, Py_ssize_t len)
{
    ps->p = buf;
    ps->end = buf + len;
    ps->stop = 0;
    JNode *n = parse_value(ps, 0);
    if (n == NULL)
        return NULL;
    skip_ws(ps);
    if (ps->p != ps->end)
        return NULL;
    return n;
}

/* ---------------- specs (converted from Python tuples, GIL-held) ------- */

typedef struct {
    const char **parts;
    uint32_t *lens;
    int n;
} CPath;

typedef struct {
    CPath *paths; /* the "parts" of one segment */
    int n;
} CSeg;

typedef struct {
    CSeg *segs;
    int n;
} CAxis;

typedef struct {
    int axis;
    CPath sub;
} CRagged;

typedef struct {
    int child, parent; /* axis indices */
} CParentSpec;

typedef struct {
    int axis;
    CPath sub;
} CRKSpec;

typedef struct {
    CPath path;
    int ns_scoped;
} CCanonSpec;

/* Per-axis subpath trie over the ragged columns that share the axis:
 * the per-column extraction loop used to re-walk every shared subpath
 * prefix per item per column (securityContext.* columns each re-found
 * securityContext).  One trie descent per item touches each prefix
 * once.  Nodes live in the spec arena; children are a sibling list
 * (ragged fan-out per level is small). */
typedef struct RTrie {
    struct RTrie *children, *sibling;
    const char *key;
    uint32_t klen;
    int col; /* ragged column whose subpath ends here, else -1 */
} RTrie;

static RTrie *
rtrie_child(RTrie *node, const char *k, uint32_t kn, Arena *ar)
{
    RTrie *c;
    for (c = node->children; c != NULL; c = c->sibling)
        if (c->klen == kn && memcmp(c->key, k, kn) == 0)
            return c;
    c = (RTrie *)arena_alloc(ar, sizeof(RTrie));
    if (c == NULL)
        return NULL;
    c->children = NULL;
    c->key = k;
    c->klen = kn;
    c->col = -1;
    c->sibling = node->children;
    node->children = c;
    return c;
}

/* ---------------- DOM helpers ---------------- */

static JNode *
obj_get(JNode *o, const char *k, uint32_t kn)
{
    if (o == NULL || o->type != JT_OBJ)
        return NULL;
    char k0 = kn ? k[0] : 0;
    for (uint32_t i = 0; i < o->n; i++) {
        /* length + first-byte reject before the memcmp call: K8s keys
         * cluster at 4-10 bytes, so length alone collides constantly */
        if (o->u.obj.klens[i] == kn &&
            (kn == 0 || (o->u.obj.keys[i][0] == k0 &&
                         memcmp(o->u.obj.keys[i], k, kn) == 0)))
            return o->u.obj.vals[i];
    }
    return NULL;
}

static JNode *
jwalk(JNode *o, const CPath *path)
{
    JNode *cur = o;
    for (int i = 0; i < path->n; i++) {
        cur = obj_get(cur, path->parts[i], path->lens[i]);
        if (cur == NULL)
            return NULL;
    }
    return cur;
}

/* classify into (kind, num, local sid) with per-thread interning */
static int
jclassify(Intern *it, JNode *v, signed char *kind, float *num, int32_t *sid)
{
    *num = 0.0f;
    *sid = -1;
    switch (v->type) {
    case JT_TRUE: *kind = K_TRUE; break;
    case JT_FALSE: *kind = K_FALSE; break;
    case JT_NUM: *kind = K_NUM; *num = (float)v->u.num; break;
    case JT_STR: {
        *kind = K_STR;
        int32_t id = intern_get(it, v->u.str, v->n);
        if (id < 0)
            return -1;
        *sid = id;
        break;
    }
    case JT_NULL: *kind = K_NULL; break;
    case JT_OBJ: *kind = K_MAP; break;
    default: *kind = K_OTHER; break; /* array */
    }
    return 0;
}

/* growable (node, key) list used during axis collection */
typedef struct {
    JNode **items;
    const char **keys;
    uint32_t *klens;
    size_t n, cap;
} NKList;

static int
nklist_reserve(NKList *l, size_t extra)
{
    if (l->n + extra <= l->cap)
        return 0;
    size_t ncap = l->cap ? l->cap * 2 : 64;
    while (ncap < l->n + extra)
        ncap *= 2;
    {
        JNode **ni = (JNode **)realloc((void *)l->items,
                                       ncap * sizeof(JNode *));
        const char **nk = (const char **)realloc((void *)l->keys,
                                                 ncap * sizeof(char *));
        uint32_t *nl = (uint32_t *)realloc(l->klens,
                                           ncap * sizeof(uint32_t));
        if (!ni || !nk || !nl) {
            if (ni) l->items = ni;
            if (nk) l->keys = nk;
            if (nl) l->klens = nl;
            return -1;
        }
        l->items = ni; l->keys = nk; l->klens = nl; l->cap = ncap;
    }
    return 0;
}

static int
nklist_push(NKList *l, JNode *n, const char *k, uint32_t kn)
{
    if (l->n == l->cap && nklist_reserve(l, 1) < 0)
        return -1;
    l->items[l->n] = n;
    l->keys[l->n] = k;
    l->klens[l->n] = kn;
    l->n++;
    return 0;
}

/* bulk-append one collected node's children (list values keyless, map
 * values with their keys) — memcpys instead of per-item pushes */
static int
nklist_extend_node(NKList *l, JNode *val)
{
    if (val->n == 0)
        return 0;
    if (nklist_reserve(l, val->n) < 0)
        return -1;
    if (val->type == JT_ARR) {
        memcpy((void *)(l->items + l->n), val->u.items,
               val->n * sizeof(JNode *));
        memset((void *)(l->keys + l->n), 0, val->n * sizeof(char *));
        memset(l->klens + l->n, 0, val->n * sizeof(uint32_t));
    } else { /* JT_OBJ */
        memcpy((void *)(l->items + l->n), val->u.obj.vals,
               val->n * sizeof(JNode *));
        memcpy((void *)(l->keys + l->n), val->u.obj.keys,
               val->n * sizeof(char *));
        memcpy(l->klens + l->n, val->u.obj.klens,
               val->n * sizeof(uint32_t));
    }
    l->n += val->n;
    return 0;
}

/* ---------------- pooled thread contexts ----------------
 *
 * A sweep calls flatten_json_batch once per chunk; the per-thread
 * arena (1MB blocks), intern table and parser/BFS scratch used to be
 * malloc'd and freed on every call.  The pool keeps them across calls
 * (acquired/released with the GIL held), so a steady-state chunk
 * re-parses into already-warm memory.  Retained arena bytes are capped
 * per context so one giant document can't pin memory forever. */

#define CTX_POOL_MAX 64
#define CTX_ARENA_KEEP (16u << 20)

typedef struct CtxCache {
    Arena arena;
    Intern intern;
    /* parser scratch stacks */
    JNode **nstack;
    const char **kstack;
    uint32_t *lstack;
    size_t scap;
    /* BFS scratch */
    NKList sa, sb, sout;
    struct CtxCache *next;
} CtxCache;

static CtxCache *g_ctx_pool;
static int g_ctx_pool_n;

/* keep at most one (bounded) block; drop the rest */
static void
arena_trim(Arena *a)
{
    ArenaBlock *keep = NULL, *b = a->head;
    while (b) {
        ArenaBlock *nx = b->next;
        if (keep == NULL && b->cap <= CTX_ARENA_KEEP)
            keep = b;
        else
            free(b);
        b = nx;
    }
    if (keep) {
        keep->used = 0;
        keep->next = NULL;
    }
    a->head = keep;
}

static CtxCache *
ctx_acquire(void)
{
    CtxCache *c = g_ctx_pool;
    if (c != NULL) {
        g_ctx_pool = c->next;
        g_ctx_pool_n--;
        c->next = NULL;
        return c;
    }
    c = (CtxCache *)calloc(1, sizeof(CtxCache));
    if (c == NULL)
        return NULL;
    if (intern_init(&c->intern) < 0) {
        free(c);
        return NULL;
    }
    return c;
}

static void
ctx_destroy(CtxCache *c)
{
    arena_free(&c->arena);
    intern_destroy(&c->intern);
    free(c->nstack);
    free((void *)c->kstack);
    free(c->lstack);
    free(c->sa.items); free((void *)c->sa.keys); free(c->sa.klens);
    free(c->sb.items); free((void *)c->sb.keys); free(c->sb.klens);
    free(c->sout.items); free((void *)c->sout.keys); free(c->sout.klens);
    free(c);
}

static void
ctx_release(CtxCache *c)
{
    if (g_ctx_pool_n >= CTX_POOL_MAX) {
        ctx_destroy(c);
        return;
    }
    arena_trim(&c->arena);
    intern_reset(&c->intern);
    c->sa.n = c->sb.n = c->sout.n = 0;
    c->next = g_ctx_pool;
    g_ctx_pool = c;
    g_ctx_pool_n++;
}

/* append items of one segment (mirrors collect_segment_keyed in
 * flattenmod.c: lists extend values keyless; maps extend values with
 * their keys). scratch a/b alternate as BFS levels. */
static int
jcollect_segment(JNode *root, const CSeg *seg, NKList *out,
                 NKList *a, NKList *b)
{
    a->n = 0;
    if (nklist_push(a, root, NULL, 0) < 0)
        return -1;
    NKList *level = a, *next = b;
    for (int p = 0; p < seg->n; p++) {
        next->n = 0;
        for (size_t i = 0; i < level->n; i++) {
            JNode *val = jwalk(level->items[i], &seg->paths[p]);
            if (val == NULL)
                continue;
            if ((val->type == JT_ARR || val->type == JT_OBJ) &&
                nklist_extend_node(next, val) < 0)
                return -1;
        }
        NKList *t = level;
        level = next;
        next = t;
    }
    if (level->n) {
        size_t base = out->n;
        if (nklist_reserve(out, level->n) < 0)
            return -1;
        memcpy((void *)(out->items + base), level->items,
               level->n * sizeof(JNode *));
        memcpy((void *)(out->keys + base), level->keys,
               level->n * sizeof(char *));
        memcpy(out->klens + base, level->klens,
               level->n * sizeof(uint32_t));
        out->n += level->n;
    }
    return 0;
}

/* sorted truthy keys of a map node (Rego {k | m[k]} semantics: value not
 * false).  Byte-wise sort == code-point sort for UTF-8. */
typedef struct {
    const char *s;
    uint32_t n;
} KeyRef;

static int
keyref_cmp(const void *pa, const void *pb)
{
    const KeyRef *a = (const KeyRef *)pa, *b = (const KeyRef *)pb;
    uint32_t m = a->n < b->n ? a->n : b->n;
    int c = memcmp(a->s, b->s, m);
    if (c)
        return c;
    return a->n < b->n ? -1 : (a->n > b->n ? 1 : 0);
}

/* label/key sets are tiny (a handful per map): insertion sort beats a
 * qsort call per item; big sets still take qsort */
static void
keyref_sort(KeyRef *keys, int c)
{
    if (c <= 1)
        return;
    if (c > 16) {
        qsort(keys, (size_t)c, sizeof(KeyRef), keyref_cmp);
        return;
    }
    for (int i = 1; i < c; i++) {
        KeyRef k = keys[i];
        int j = i - 1;
        while (j >= 0 && keyref_cmp(&keys[j], &k) > 0) {
            keys[j + 1] = keys[j];
            j--;
        }
        keys[j + 1] = k;
    }
}

/* collect truthy keys of map node into arena array; returns count */
static int
truthy_keys(Arena *arena, JNode *val, KeyRef **out)
{
    if (val == NULL || val->type != JT_OBJ) {
        *out = NULL;
        return 0;
    }
    KeyRef *keys = (KeyRef *)arena_alloc(arena,
                                         (val->n ? val->n : 1) *
                                         sizeof(KeyRef));
    if (keys == NULL)
        return -1;
    int c = 0;
    for (uint32_t i = 0; i < val->n; i++) {
        if (val->u.obj.vals[i]->type == JT_FALSE)
            continue;
        keys[c].s = val->u.obj.keys[i];
        keys[c].n = val->u.obj.klens[i];
        c++;
    }
    keyref_sort(keys, c);
    *out = keys;
    return c;
}

/* canonical selector encoding (selector_canon in ops/flatten.py): the
 * ','-joined byte-wise sort of "key:value" over the STRING pairs of the
 * map at the spec path ("" for scalars/arrays/absent maps — OPA's
 * non-strict builtin-error semantics skip non-string pairs).  ns-scoped
 * specs prefix "ns\0"; a non-string namespace leaves the column at its
 * -2 default (the rule's ns assignment yields nothing).  Byte-wise pair
 * sort == code-point sort for UTF-8, matching Python sorted(). */
static int
canon_row(Arena *arena, Intern *intern, JNode *root, const CPath *path,
          int ns_scoped, int32_t *out)
{
    if (root == NULL)
        return 0; /* non-object document: stays -2 */
    const char *ns = NULL;
    uint32_t nsn = 0;
    if (ns_scoped) {
        JNode *meta = obj_get(root, "metadata", 8);
        JNode *nsv = meta ? obj_get(meta, "namespace", 9) : NULL;
        if (nsv == NULL || nsv->type != JT_STR)
            return 0; /* stays -2 */
        ns = nsv->u.str;
        nsn = nsv->n;
    }
    JNode *val = jwalk(root, path);
    KeyRef *pairs = NULL;
    size_t total = 0;
    int c = 0;
    if (val != NULL && val->type == JT_OBJ && val->n) {
        pairs = (KeyRef *)arena_alloc(arena, val->n * sizeof(KeyRef));
        if (pairs == NULL)
            return -1;
        for (uint32_t i = 0; i < val->n; i++) {
            JNode *v = val->u.obj.vals[i];
            if (v->type != JT_STR)
                continue;
            uint32_t kn = val->u.obj.klens[i];
            uint32_t pn = kn + 1 + v->n;
            char *pb = (char *)arena_alloc(arena, pn);
            if (pb == NULL)
                return -1;
            memcpy(pb, val->u.obj.keys[i], kn);
            pb[kn] = ':';
            memcpy(pb + kn + 1, v->u.str, v->n);
            pairs[c].s = pb;
            pairs[c].n = pn;
            total += pn;
            c++;
        }
        keyref_sort(pairs, c);
    }
    size_t len = (ns_scoped ? (size_t)nsn + 1 : 0) + total +
                 (c ? (size_t)c - 1 : 0);
    char *buf = (char *)arena_alloc(arena, len ? len : 1);
    if (buf == NULL)
        return -1;
    size_t o = 0;
    if (ns_scoped) {
        memcpy(buf, ns, nsn);
        o = nsn;
        buf[o++] = '\0';
    }
    for (int i = 0; i < c; i++) {
        if (i)
            buf[o++] = ',';
        memcpy(buf + o, pairs[i].s, pairs[i].n);
        o += pairs[i].n;
    }
    int32_t id = intern_get(intern, buf, (uint32_t)o);
    if (id < 0)
        return -1;
    *out = id;
    return 0;
}

/* ---------------- work context ---------------- */

typedef struct {
    JNode **items;
    const char **keys;
    uint32_t *klens;
    uint32_t *seg_counts; /* items contributed per axis segment */
    int count;
} AxisItems;

typedef struct {
    KeyRef *keys;
    int count;
} KeysetRow;

typedef struct {
    KeyRef **item_keys;
    int *item_counts;
    int n_items;
} RKRow;

typedef struct {
    JNode *root;
    AxisItems *axes;   /* n_axes */
    KeysetRow *keysets; /* n_keysets */
    RKRow *rks;         /* n_rks */
} Row;

struct Work;

typedef struct {
    struct Work *w;
    int tid;
    Py_ssize_t row0, row1;
    CtxCache *cc;  /* pooled backing store of the four fields below */
    Arena arena;
    Intern intern;
    Parser parser;
    NKList sa, sb, sout;
    int err; /* 0 ok, 1 oom, 2 parse error */
    Py_ssize_t err_row;
    Py_ssize_t *max_axis;   /* per axis */
    Py_ssize_t *max_keyset; /* per keyset */
    Py_ssize_t *max_rk_l;   /* per rk spec */
    int32_t *remap;         /* local id -> global id */
    size_t filled;          /* bytes of prefill this thread wrote */
    pthread_t thread;
} ThreadCtx;

/* An output array: made PyArray_EMPTY under the GIL and entered here; the
 * workers write the prefill of their own rows (and the last one the
 * padding rows') inside the released phase, before they write a cell of
 * them. */
typedef struct {
    char *data;
    size_t row_bytes; /* bytes of one row of axis 0 */
    int fill;         /* 0 and -1: that byte throughout; else an int32 */
} FillEnt;

typedef struct {
    FillEnt *ents;
    int n, cap;
    size_t held; /* prefill bytes written under the GIL instead */
} FillList;

typedef struct Work {
    const char **bufs;
    Py_ssize_t *blens;
    Py_ssize_t n_real, n_pad;
    CPath *scalars;
    int *scalar_review; /* 1 if path starts with __review__ (synth) */
    int n_scalars;
    CAxis *axes;
    int n_axes;
    CRagged *raggeds;
    int n_raggeds;
    /* per-axis ragged extraction plan (built from raggeds, GIL-held) */
    RTrie **ax_trie;     /* subpath trie per axis (NULL: none) */
    int **ax_self;       /* ragged cols whose subpath is the item itself */
    int *ax_nself;
    Py_ssize_t *ax_m;    /* padded width shared by the axis's raggeds */
    RTrie *sc_trie;      /* path trie over the non-review scalars */
    int *sc_self;        /* scalar cols whose path is the root itself */
    int sc_nself;
    CPath *keysets;
    int n_keysets;
    int *mk_axes;
    int n_mk;
    CParentSpec *parents;
    int n_parents;
    CRKSpec *rks;
    int n_rks;
    CCanonSpec *canons;
    int n_canons;
    long bucket;
    /* least widths the caller asks for (0: none): per axis, per keyset,
     * per ragged keyset's l */
    Py_ssize_t *ax_floor, *ks_floor, *rk_floor;
    Row *rows;          /* malloc'd: each worker zeroes and wires its own */
    AxisItems *ax_blk;  /* rows' sub-arrays, one block a kind */
    KeysetRow *ks_blk;
    RKRow *rk_blk;
    FillList fill1, fill2; /* prefills the workers owe, by phase */
    /* phase-1 outputs */
    int32_t *gid, *kid, *nsid, *nmid;
    int32_t **c_sid; /* canon columns [N], -2 = idiom yields nothing */
    uint8_t *genname;
    signed char **s_kind;
    float **s_num;
    int32_t **s_sid;
    int32_t **a_count;
    /* phase-2 outputs */
    signed char **r_kind;
    float **r_num;
    int32_t **r_sid;
    Py_ssize_t *r_m;
    int32_t **k_sid, **k_cnt;
    Py_ssize_t *k_l;
    int32_t **mk_sid;
    Py_ssize_t *mk_m;
    int32_t **p_idx;
    Py_ssize_t *p_m;
    int32_t **rk_sid, **rk_cnt;
    Py_ssize_t *rk_m, *rk_l;
    int phase;
    int nthreads;
    ThreadCtx *tc;
} Work;

static int trie_extract(ThreadCtx *t, const RTrie *node, JNode *obj,
                        signed char **kind, float **num, int32_t **sid,
                        Py_ssize_t off);

static long
bucket_up(long n, long bucket)
{
    if (n <= 0)
        return bucket;
    return ((n + bucket - 1) / bucket) * bucket;
}

/* synthesize a __review__-rooted scalar (audit sweeps: _synth_review in
 * ops/flatten.py — kind{group,version,kind}, operation "", name,
 * namespace). */
static int
synth_review_scalar(ThreadCtx *t, JNode *root, const CPath *path,
                    signed char *kind, float *num, int32_t *sid)
{
    *num = 0.0f;
    *sid = -1;
    const char **parts = path->parts;
    uint32_t *lens = path->lens;
    int n = path->n; /* includes leading __review__ */
    if (n == 1) {
        *kind = K_MAP;
        return 0;
    }
    const char *p1 = parts[1];
    uint32_t l1 = lens[1];
    JNode *av = obj_get(root, "apiVersion", 10);
    const char *avs = (av && av->type == JT_STR) ? av->u.str : "";
    uint32_t avn = (av && av->type == JT_STR) ? av->n : 0;
    if (l1 == 4 && memcmp(p1, "kind", 4) == 0) {
        if (n == 2) {
            *kind = K_MAP;
            return 0;
        }
        if (n > 3) {
            *kind = K_ABSENT;
            return 0;
        }
        const char *p2 = parts[2];
        uint32_t l2 = lens[2];
        /* split apiVersion at first '/' */
        const char *slash = (const char *)memchr(avs, '/', avn);
        const char *g = "", *v = avs;
        uint32_t gn = 0, vn = avn;
        if (slash != NULL) {
            g = avs;
            gn = (uint32_t)(slash - avs);
            v = slash + 1;
            vn = avn - gn - 1;
        }
        const char *out = NULL;
        uint32_t outn = 0;
        if (l2 == 5 && memcmp(p2, "group", 5) == 0) {
            out = g; outn = gn;
        } else if (l2 == 7 && memcmp(p2, "version", 7) == 0) {
            out = v; outn = vn;
        } else if (l2 == 4 && memcmp(p2, "kind", 4) == 0) {
            JNode *k = obj_get(root, "kind", 4);
            out = (k && k->type == JT_STR) ? k->u.str : "";
            outn = (k && k->type == JT_STR) ? k->n : 0;
        } else {
            *kind = K_ABSENT;
            return 0;
        }
        *kind = K_STR;
        int32_t id = intern_get(&t->intern, out, outn);
        if (id < 0)
            return -1;
        *sid = id;
        return 0;
    }
    if (n != 2) {
        *kind = K_ABSENT;
        return 0;
    }
    const char *out = NULL;
    uint32_t outn = 0;
    if (l1 == 9 && memcmp(p1, "operation", 9) == 0) {
        out = "";
        outn = 0;
    } else if ((l1 == 4 && memcmp(p1, "name", 4) == 0) ||
               (l1 == 9 && memcmp(p1, "namespace", 9) == 0)) {
        JNode *meta = obj_get(root, "metadata", 8);
        JNode *f = meta ? obj_get(meta, p1, l1) : NULL;
        out = (f && f->type == JT_STR) ? f->u.str : "";
        outn = (f && f->type == JT_STR) ? f->n : 0;
    } else {
        *kind = K_ABSENT;
        return 0;
    }
    *kind = K_STR;
    int32_t id = intern_get(&t->intern, out, outn);
    if (id < 0)
        return -1;
    *sid = id;
    return 0;
}

static int
phase1_row(ThreadCtx *t, Py_ssize_t i)
{
    Work *w = t->w;
    t->parser.arena = &t->arena;
    JNode *root = parse_doc(&t->parser, w->bufs[i], w->blens[i]);
    if (root == NULL) {
        t->err = t->parser.err ? 1 : 2;
        t->err_row = i;
        return -1;
    }
    if (root->type != JT_OBJ)
        root = NULL; /* non-object doc: behave as empty row */
    Row *row = &w->rows[i];
    row->root = root;

    /* identity */
    JNode *av = obj_get(root, "apiVersion", 10);
    const char *avs = (av && av->type == JT_STR) ? av->u.str : "";
    uint32_t avn = (av && av->type == JT_STR) ? av->n : 0;
    const char *slash = (const char *)memchr(avs, '/', avn);
    int32_t gidv;
    if (slash != NULL)
        gidv = intern_get(&t->intern, avs, (uint32_t)(slash - avs));
    else
        gidv = intern_get(&t->intern, "", 0);
    if (gidv < 0)
        goto oom;
    w->gid[i] = gidv;
    JNode *kv = obj_get(root, "kind", 4);
    int32_t kidv = (kv && kv->type == JT_STR)
        ? intern_get(&t->intern, kv->u.str, kv->n)
        : intern_get(&t->intern, "", 0);
    if (kidv < 0)
        goto oom;
    w->kid[i] = kidv;
    JNode *meta = obj_get(root, "metadata", 8);
    JNode *ns = meta ? obj_get(meta, "namespace", 9) : NULL;
    JNode *nm = meta ? obj_get(meta, "name", 4) : NULL;
    int32_t nsv = (ns && ns->type == JT_STR)
        ? intern_get(&t->intern, ns->u.str, ns->n)
        : intern_get(&t->intern, "", 0);
    if (nsv < 0)
        goto oom;
    w->nsid[i] = nsv;
    int32_t nmv = (nm && nm->type == JT_STR)
        ? intern_get(&t->intern, nm->u.str, nm->n)
        : intern_get(&t->intern, "", 0);
    if (nmv < 0)
        goto oom;
    w->nmid[i] = nmv;
    w->genname[i] = (meta && obj_get(meta, "generateName", 12)) ? 1 : 0;

    /* scalars: review-synth columns one by one; the rest through one
     * path-trie descent (absent values keep the arrays' prefill, which
     * equals the defaults the per-column loop used to write) */
    for (int s = 0; s < w->n_scalars; s++) {
        if (!w->scalar_review[s])
            continue;
        signed char k = 0;
        float nmb = 0.0f;
        int32_t sd = -1;
        if (synth_review_scalar(t, root, &w->scalars[s], &k, &nmb,
                                &sd) < 0)
            goto oom;
        w->s_kind[s][i] = k;
        w->s_num[s][i] = nmb;
        w->s_sid[s][i] = sd;
    }
    if (root != NULL) {
        for (int q = 0; q < w->sc_nself; q++) {
            int s = w->sc_self[q];
            if (jclassify(&t->intern, root, &w->s_kind[s][i],
                          &w->s_num[s][i], &w->s_sid[s][i]) < 0)
                goto oom;
        }
        if (w->sc_trie != NULL &&
            trie_extract(t, w->sc_trie, root, w->s_kind, w->s_num,
                         w->s_sid, i) < 0)
            goto oom;
    }

    /* axes */
    for (int a = 0; a < w->n_axes; a++) {
        t->sout.n = 0;
        const CAxis *ax = &w->axes[a];
        AxisItems *ai = &row->axes[a];
        /* per-segment contribution counts let phase-2 parent-idx slice
         * this enumeration instead of re-walking the DOM per row */
        ai->seg_counts = (uint32_t *)arena_alloc(
            &t->arena, (size_t)(ax->n ? ax->n : 1) * sizeof(uint32_t));
        if (ai->seg_counts == NULL)
            goto oom;
        for (int g = 0; g < ax->n; g++) {
            size_t before = t->sout.n;
            if (jcollect_segment(root, &ax->segs[g], &t->sout, &t->sa,
                                 &t->sb) < 0)
                goto oom;
            ai->seg_counts[g] = (uint32_t)(t->sout.n - before);
        }
        size_t c = t->sout.n;
        ai->count = (int)c;
        if (c) {
            ai->items = (JNode **)arena_alloc(&t->arena,
                                              c * sizeof(JNode *));
            ai->keys = (const char **)arena_alloc(&t->arena,
                                                  c * sizeof(char *));
            ai->klens = (uint32_t *)arena_alloc(&t->arena,
                                                c * sizeof(uint32_t));
            if (!ai->items || !ai->keys || !ai->klens)
                goto oom;
            memcpy((void *)ai->items, t->sout.items, c * sizeof(JNode *));
            memcpy((void *)ai->keys, t->sout.keys, c * sizeof(char *));
            memcpy(ai->klens, t->sout.klens, c * sizeof(uint32_t));
        }
        w->a_count[a][i] = (int32_t)c;
        if ((Py_ssize_t)c > t->max_axis[a])
            t->max_axis[a] = (Py_ssize_t)c;
    }

    /* flat keysets */
    for (int s = 0; s < w->n_keysets; s++) {
        JNode *val = jwalk(root, &w->keysets[s]);
        KeyRef *keys = NULL;
        int c = truthy_keys(&t->arena, val, &keys);
        if (c < 0)
            goto oom;
        row->keysets[s].keys = keys;
        row->keysets[s].count = c;
        if (c > t->max_keyset[s])
            t->max_keyset[s] = c;
    }

    /* canonical-selector columns */
    for (int s = 0; s < w->n_canons; s++) {
        if (canon_row(&t->arena, &t->intern, root, &w->canons[s].path,
                      w->canons[s].ns_scoped, &w->c_sid[s][i]) < 0)
            goto oom;
    }

    /* ragged keysets: per-item truthy keys (clipping to m happens in
     * phase 2; key extraction covers all items) */
    for (int s = 0; s < w->n_rks; s++) {
        const CRKSpec *spec = &w->rks[s];
        AxisItems *ai = &row->axes[spec->axis];
        RKRow *rk = &row->rks[s];
        rk->n_items = ai->count;
        if (ai->count == 0) {
            rk->item_keys = NULL;
            rk->item_counts = NULL;
            continue;
        }
        rk->item_keys = (KeyRef **)arena_alloc(
            &t->arena, (size_t)ai->count * sizeof(KeyRef *));
        rk->item_counts = (int *)arena_alloc(
            &t->arena, (size_t)ai->count * sizeof(int));
        if (!rk->item_keys || !rk->item_counts)
            goto oom;
        for (int j = 0; j < ai->count; j++) {
            JNode *val = spec->sub.n
                ? jwalk(ai->items[j], &spec->sub)
                : ai->items[j];
            KeyRef *keys = NULL;
            int c = truthy_keys(&t->arena, val, &keys);
            if (c < 0)
                goto oom;
            rk->item_keys[j] = keys;
            rk->item_counts[j] = c;
            if (c > t->max_rk_l[s])
                t->max_rk_l[s] = c;
        }
    }
    return 0;
oom:
    t->err = 1;
    t->err_row = i;
    return -1;
}

static int
trie_extract(ThreadCtx *t, const RTrie *node, JNode *obj,
             signed char **kind, float **num, int32_t **sid,
             Py_ssize_t off)
{
    for (const RTrie *c = node->children; c != NULL; c = c->sibling) {
        JNode *v = obj_get(obj, c->key, c->klen);
        if (v == NULL)
            continue;
        if (c->col >= 0 &&
            jclassify(&t->intern, v, &kind[c->col][off],
                      &num[c->col][off], &sid[c->col][off]) < 0)
            return -1;
        if (c->children != NULL &&
            trie_extract(t, c, v, kind, num, sid, off) < 0)
            return -1;
    }
    return 0;
}

static int
phase2_row(ThreadCtx *t, Py_ssize_t i)
{
    Work *w = t->w;
    Row *row = &w->rows[i];

    /* ragged columns, grouped per axis: one trie descent per item
     * covers every subpath column (shared prefixes walk once) */
    for (int a = 0; a < w->n_axes; a++) {
        const RTrie *tr = w->ax_trie[a];
        int nself = w->ax_nself[a];
        if (tr == NULL && nself == 0)
            continue;
        AxisItems *ai = &row->axes[a];
        Py_ssize_t m = w->ax_m[a];
        int cnt = ai->count;
        if ((Py_ssize_t)cnt > m)
            cnt = (int)m;
        for (int j = 0; j < cnt; j++) {
            JNode *item = ai->items[j];
            Py_ssize_t off = i * m + j;
            for (int s = 0; s < nself; s++) {
                int r = w->ax_self[a][s];
                if (jclassify(&t->intern, item, &w->r_kind[r][off],
                              &w->r_num[r][off], &w->r_sid[r][off]) < 0)
                    goto oom;
            }
            if (tr != NULL &&
                trie_extract(t, tr, item, w->r_kind, w->r_num,
                             w->r_sid, off) < 0)
                goto oom;
        }
    }

    for (int s = 0; s < w->n_keysets; s++) {
        KeysetRow *kr = &row->keysets[s];
        Py_ssize_t l = w->k_l[s];
        w->k_cnt[s][i] = (int32_t)kr->count;
        int cnt = kr->count;
        if ((Py_ssize_t)cnt > l)
            cnt = (int)l;
        for (int j = 0; j < cnt; j++) {
            int32_t id = intern_get(&t->intern, kr->keys[j].s,
                                    kr->keys[j].n);
            if (id < 0)
                goto oom;
            w->k_sid[s][i * l + j] = id;
        }
    }

    for (int q = 0; q < w->n_mk; q++) {
        AxisItems *ai = &row->axes[w->mk_axes[q]];
        Py_ssize_t m = w->mk_m[q];
        int cnt = ai->count;
        if ((Py_ssize_t)cnt > m)
            cnt = (int)m;
        for (int j = 0; j < cnt; j++) {
            if (ai->keys[j] == NULL)
                continue;
            int32_t id = intern_get(&t->intern, ai->keys[j], ai->klens[j]);
            if (id < 0)
                goto oom;
            w->mk_sid[q][i * m + j] = id;
        }
    }

    /* parent-idx: ordinal of each child item's parent in the parent
     * axis's enumeration (mirrors extract_extras in flattenmod.c).
     * The parent axis was already enumerated in phase 1 — its
     * seg_counts slice that enumeration per segment, so no DOM re-walk
     * happens here. */
    for (int p = 0; p < w->n_parents; p++) {
        const CAxis *cax = &w->axes[w->parents[p].child];
        const CAxis *pax = &w->axes[w->parents[p].parent];
        const AxisItems *pai = &row->axes[w->parents[p].parent];
        Py_ssize_t m = w->p_m[p];
        Py_ssize_t j = 0, base = 0;
        size_t poff = 0;
        int nseg = cax->n < pax->n ? cax->n : pax->n;
        for (int g = 0; g < nseg; g++) {
            const CSeg *cseg = &cax->segs[g];
            const CPath *sub = &cseg->paths[cseg->n - 1];
            size_t npar = pai->seg_counts[g];
            for (size_t k = 0; k < npar; k++) {
                JNode *val = jwalk(pai->items[poff + k], sub);
                if (val == NULL)
                    continue;
                if (val->type == JT_ARR || val->type == JT_OBJ) {
                    for (uint32_t q2 = 0; q2 < val->n && j < m; q2++)
                        w->p_idx[p][i * m + j++] =
                            (int32_t)(base + (Py_ssize_t)k);
                }
            }
            poff += npar;
            base += (Py_ssize_t)npar;
        }
    }

    for (int s = 0; s < w->n_rks; s++) {
        RKRow *rk = &row->rks[s];
        Py_ssize_t m = w->rk_m[s], l = w->rk_l[s];
        int cnt = rk->n_items;
        if ((Py_ssize_t)cnt > m)
            cnt = (int)m;
        for (int j = 0; j < cnt; j++) {
            w->rk_cnt[s][i * m + j] = (int32_t)rk->item_counts[j];
            KeyRef *keys = rk->item_keys[j];
            int kc = rk->item_counts[j];
            if ((Py_ssize_t)kc > l)
                kc = (int)l;
            for (int q = 0; q < kc; q++) {
                int32_t id = intern_get(&t->intern, keys[q].s, keys[q].n);
                if (id < 0)
                    goto oom;
                w->rk_sid[s][(i * m + j) * l + q] = id;
            }
        }
    }
    return 0;
oom:
    t->err = 1;
    t->err_row = i;
    return -1;
}

static void
remap_range(const int32_t *remap, int32_t *arr, Py_ssize_t lo,
            Py_ssize_t hi)
{
    for (Py_ssize_t i = lo; i < hi; i++) {
        if (arr[i] >= 0)
            arr[i] = remap[arr[i]];
    }
}

static void
phase3_remap(ThreadCtx *t)
{
    Work *w = t->w;
    const int32_t *rm = t->remap;
    Py_ssize_t r0 = t->row0, r1 = t->row1;
    remap_range(rm, w->gid, r0, r1);
    remap_range(rm, w->kid, r0, r1);
    remap_range(rm, w->nsid, r0, r1);
    remap_range(rm, w->nmid, r0, r1);
    for (int s = 0; s < w->n_scalars; s++)
        remap_range(rm, w->s_sid[s], r0, r1);
    for (int s = 0; s < w->n_canons; s++)
        remap_range(rm, w->c_sid[s], r0, r1);
    for (int r = 0; r < w->n_raggeds; r++)
        remap_range(rm, w->r_sid[r], r0 * w->r_m[r], r1 * w->r_m[r]);
    for (int s = 0; s < w->n_keysets; s++)
        remap_range(rm, w->k_sid[s], r0 * w->k_l[s], r1 * w->k_l[s]);
    for (int q = 0; q < w->n_mk; q++)
        remap_range(rm, w->mk_sid[q], r0 * w->mk_m[q], r1 * w->mk_m[q]);
    for (int s = 0; s < w->n_rks; s++)
        remap_range(rm, w->rk_sid[s], r0 * w->rk_m[s] * w->rk_l[s],
                    r1 * w->rk_m[s] * w->rk_l[s]);
}

static void
fill_bytes(char *p, size_t nbytes, int fill)
{
    if (fill == 0 || fill == -1) {
        /* int32 -1 is all-ones bytes: one vectorized memset instead of
         * an element loop (the sid arrays are the bulk of the output) */
        memset(p, fill, nbytes);
    } else {
        int32_t *data = (int32_t *)p;
        size_t total = nbytes / sizeof(int32_t);
        for (size_t i = 0; i < total; i++)
            data[i] = fill;
    }
}

/* rows [r0, r1) of every listed array: row-major, so one contiguous
 * stretch an array */
static size_t
fill_rows(const FillList *fl, Py_ssize_t r0, Py_ssize_t r1)
{
    size_t total = 0;
    if (r1 <= r0)
        return 0;
    for (int i = 0; i < fl->n; i++) {
        const FillEnt *e = &fl->ents[i];
        size_t nbytes = (size_t)(r1 - r0) * e->row_bytes;
        fill_bytes(e->data + (size_t)r0 * e->row_bytes, nbytes, e->fill);
        total += nbytes;
    }
    return total;
}

/* a worker's share of a phase's prefill: its own rows, and for the last
 * thread (whatever its own range holds) the padding rows past n_real.
 * The rows are partitioned by thread, so no thread waits for another. */
static void
worker_fill(ThreadCtx *t, const FillList *fl)
{
    Work *w = t->w;
    t->filled += fill_rows(fl, t->row0, t->row1);
    if (t->tid == w->nthreads - 1)
        t->filled += fill_rows(fl, w->n_real, w->n_pad);
}

/* the scratch rows of a worker's own range: zeroed and pointed at their
 * slices of the three blocks (first touched here, not under the GIL) */
static void
worker_rows(ThreadCtx *t)
{
    Work *w = t->w;
    size_t r0 = (size_t)t->row0, n = (size_t)(t->row1 - t->row0);
    size_t na = (size_t)(w->n_axes ? w->n_axes : 1);
    size_t nk = (size_t)(w->n_keysets ? w->n_keysets : 1);
    size_t nr = (size_t)(w->n_rks ? w->n_rks : 1);
    if (n == 0)
        return;
    memset(w->ax_blk + r0 * na, 0, n * na * sizeof(AxisItems));
    memset(w->ks_blk + r0 * nk, 0, n * nk * sizeof(KeysetRow));
    memset(w->rk_blk + r0 * nr, 0, n * nr * sizeof(RKRow));
    for (size_t i = r0; i < r0 + n; i++) {
        w->rows[i].root = NULL;
        w->rows[i].axes = w->ax_blk + i * na;
        w->rows[i].keysets = w->ks_blk + i * nk;
        w->rows[i].rks = w->rk_blk + i * nr;
    }
}

static void *
worker_main(void *arg)
{
    ThreadCtx *t = (ThreadCtx *)arg;
    Work *w = t->w;
    if (w->phase == 1) {
        worker_rows(t);
        worker_fill(t, &w->fill1);
        for (Py_ssize_t i = t->row0; i < t->row1; i++)
            if (phase1_row(t, i) < 0)
                break;
    } else if (w->phase == 2) {
        worker_fill(t, &w->fill2);
        if (!t->err) {
            for (Py_ssize_t i = t->row0; i < t->row1; i++)
                if (phase2_row(t, i) < 0)
                    break;
        }
    } else {
        phase3_remap(t);
    }
    return NULL;
}

/* The released clock: the CPU seconds the calling thread burnt between
 * Py_BEGIN_ALLOW_THREADS and Py_END_ALLOW_THREADS, on the clock of
 * time.thread_time(), kept per thread.  With nthreads > 1 that is the
 * spawning and joining of the pthreads, whose own CPU no thread of the
 * interpreter's is charged; with one thread it is the phase itself.
 * ops/native.released_thread_time() sums it with the wire pack's, so a
 * stage's account can say held = cpu - released (PERF.md section 3). */
static _Thread_local double released_s;

static double
thread_cpu_s(void)
{
    struct timespec ts;
    if (clock_gettime(CLOCK_THREAD_CPUTIME_ID, &ts) != 0)
        return 0.0;
    return (double)ts.tv_sec + (double)ts.tv_nsec * 1e-9;
}

static int
run_phase(Work *w, int phase)
{
    w->phase = phase;
    if (w->nthreads == 1) {
        worker_main(&w->tc[0]);
        return 0;
    }
    for (int t = 0; t < w->nthreads; t++) {
        if (pthread_create(&w->tc[t].thread, NULL, worker_main,
                           &w->tc[t]) != 0) {
            /* fall back: run remaining contexts inline */
            for (int u = t; u < w->nthreads; u++)
                worker_main(&w->tc[u]);
            for (int u = 0; u < t; u++)
                pthread_join(w->tc[u].thread, NULL);
            return 0;
        }
    }
    for (int t = 0; t < w->nthreads; t++)
        pthread_join(w->tc[t].thread, NULL);
    return 0;
}

/* one phase with the GIL released, booked on the released clock */
static void
run_phase_released(Work *w, int phase)
{
    Py_BEGIN_ALLOW_THREADS
    double c0 = thread_cpu_s();
    run_phase(w, phase);
    released_s += thread_cpu_s() - c0;
    Py_END_ALLOW_THREADS
}

/* ---------------- GIL-side glue ---------------- */

/* One output array: PyArray_EMPTY, its prefill left to the workers through
 * ``fl``.  (Not PyArray_ZEROS for the zero-filled ones: numpy lets the GIL
 * go around every calloc of a kilobyte or more, so beside a thread that
 * wants the lock each such array costs this one a switch interval, five
 * milliseconds, to get it back.)  Only where the list cannot grow is the
 * prefill written here, GIL held, and counted in ``held``. */
static PyArrayObject *
new_arr(FillList *fl, int nd, npy_intp *dims, int typenum, int fill)
{
    PyArrayObject *a = (PyArrayObject *)PyArray_EMPTY(nd, dims, typenum, 0);
    if (a == NULL)
        return NULL;
    if (fl->n == fl->cap) {
        int cap = fl->cap ? fl->cap * 2 : 64;
        FillEnt *ents = (FillEnt *)realloc(fl->ents,
                                           (size_t)cap * sizeof(FillEnt));
        if (ents == NULL) {
            fill_bytes((char *)PyArray_DATA(a), (size_t)PyArray_NBYTES(a),
                       fill);
            fl->held += (size_t)PyArray_NBYTES(a);
            return a;
        }
        fl->ents = ents;
        fl->cap = cap;
    }
    FillEnt *e = &fl->ents[fl->n++];
    e->data = (char *)PyArray_DATA(a);
    e->row_bytes = dims[0] ? (size_t)PyArray_NBYTES(a) / (size_t)dims[0] : 0;
    e->fill = fill;
    return a;
}

static int
cpath_conv(PyObject *tup, CPath *out, Arena *ar)
{
    Py_ssize_t n = PyTuple_GET_SIZE(tup);
    out->n = (int)n;
    out->parts = (const char **)arena_alloc(ar, (n ? n : 1) *
                                            sizeof(char *));
    out->lens = (uint32_t *)arena_alloc(ar, (n ? n : 1) *
                                        sizeof(uint32_t));
    if (!out->parts || !out->lens)
        return -1;
    for (Py_ssize_t i = 0; i < n; i++) {
        Py_ssize_t len;
        const char *s = PyUnicode_AsUTF8AndSize(PyTuple_GET_ITEM(tup, i),
                                                &len);
        if (s == NULL)
            return -1;
        out->parts[i] = s;
        out->lens[i] = (uint32_t)len;
    }
    return 0;
}

static int
caxis_conv(PyObject *segments, CAxis *out, Arena *ar)
{
    Py_ssize_t n = PyTuple_GET_SIZE(segments);
    out->n = (int)n;
    out->segs = (CSeg *)arena_alloc(ar, (n ? n : 1) * sizeof(CSeg));
    if (out->segs == NULL)
        return -1;
    for (Py_ssize_t g = 0; g < n; g++) {
        PyObject *seg = PyTuple_GET_ITEM(segments, g);
        Py_ssize_t np_ = PyTuple_GET_SIZE(seg);
        CSeg *cs = &out->segs[g];
        cs->n = (int)np_;
        cs->paths = (CPath *)arena_alloc(ar, (np_ ? np_ : 1) *
                                         sizeof(CPath));
        if (cs->paths == NULL)
            return -1;
        for (Py_ssize_t p = 0; p < np_; p++) {
            if (cpath_conv(PyTuple_GET_ITEM(seg, p), &cs->paths[p], ar) < 0)
                return -1;
        }
    }
    return 0;
}

/* item ``which`` of the floors triple into ``out[n]``; a TypeError (never
 * a ValueError, which the caller reads as a document the parser refused)
 * where the shape is not the specs' */
static int
floors_conv(PyObject *floors, Py_ssize_t which, Py_ssize_t *out, int n)
{
    PyObject *seq = NULL;
    if (PyTuple_Check(floors) && PyTuple_GET_SIZE(floors) == 3)
        seq = PySequence_Fast(PyTuple_GET_ITEM(floors, which),
                              "floors: three sequences of ints");
    else
        PyErr_SetString(PyExc_TypeError,
                        "floors: None or a tuple of three sequences");
    if (seq == NULL)
        return -1;
    if (PySequence_Fast_GET_SIZE(seq) != n) {
        PyErr_SetString(PyExc_TypeError,
                        "floors: one width a spec, in the specs' order");
        Py_DECREF(seq);
        return -1;
    }
    for (int i = 0; i < n; i++) {
        out[i] = PyLong_AsSsize_t(PySequence_Fast_GET_ITEM(seq, i));
        if (out[i] == -1 && PyErr_Occurred()) {
            Py_DECREF(seq);
            return -1;
        }
    }
    Py_DECREF(seq);
    return 0;
}

static void
work_free(Work *w, Py_buffer *views, Py_ssize_t n_views, Arena *spec_arena)
{
    if (w->tc) {
        for (int t = 0; t < w->nthreads; t++) {
            ThreadCtx *tc = &w->tc[t];
            if (tc->cc != NULL) {
                /* hand the (possibly realloc'd) scratch back to the pool */
                tc->cc->arena = tc->arena;
                tc->cc->intern = tc->intern;
                tc->cc->nstack = tc->parser.nstack;
                tc->cc->kstack = tc->parser.kstack;
                tc->cc->lstack = tc->parser.lstack;
                tc->cc->scap = tc->parser.scap;
                tc->cc->sa = tc->sa;
                tc->cc->sb = tc->sb;
                tc->cc->sout = tc->sout;
                ctx_release(tc->cc);
            }
            free(tc->max_axis);
            free(tc->max_keyset);
            free(tc->max_rk_l);
            free(tc->remap);
        }
        free(w->tc);
    }
    free(w->rows); free(w->ax_blk); free(w->ks_blk); free(w->rk_blk);
    free(w->scalars); free(w->scalar_review);
    free(w->axes); free(w->raggeds); free(w->keysets); free(w->mk_axes);
    free(w->parents); free(w->rks); free(w->canons); free(w->c_sid);
    free(w->sc_self);
    free(w->ax_floor); free(w->ks_floor); free(w->rk_floor);
    free(w->fill1.ents); free(w->fill2.ents);
    free(w->ax_trie); free(w->ax_self); free(w->ax_nself); free(w->ax_m);
    free(w->s_kind); free(w->s_num); free(w->s_sid);
    free(w->a_count);
    free(w->r_kind); free(w->r_num); free(w->r_sid); free(w->r_m);
    free(w->k_sid); free(w->k_cnt); free(w->k_l);
    free(w->mk_sid); free(w->mk_m);
    free(w->p_idx); free(w->p_m);
    free(w->rk_sid); free(w->rk_cnt); free(w->rk_m); free(w->rk_l);
    free((void *)w->bufs); free(w->blens);
    if (views) {
        for (Py_ssize_t i = 0; i < n_views; i++)
            if (views[i].obj)
                PyBuffer_Release(&views[i]);
        free(views);
    }
    arena_free(spec_arena);
}

/* flatten_json_batch(items, scalars, axes, raggeds, keysets, map_key_axes,
 *                    parent_specs, rk_specs, canons, to_id, to_str,
 *                    pad_n, bucket, nthreads[, floors]) -> dict
 *
 *   items:        list of bytes-like (one JSON document per object)
 *   scalars:      list[tuple[str, ...]] (paths; __review__-rooted paths
 *                 are synthesized from object identity, the audit case)
 *   axes:         list[segments] as in flatten_batch
 *   raggeds:      list[(axis_idx, subpath)]
 *   keysets:      list[path]
 *   map_key_axes: list[int]
 *   parent_specs: list[(child_axis_idx, parent_axis_idx)]
 *   rk_specs:     list[(axis_idx, subpath)]
 *   floors:       None, or (per axis, per keyset, per rk spec) sequences
 *                 of ints: the least width of each axis and the least l
 *                 of each keyset and ragged keyset (0: none).  A width is
 *                 bucket_up(max(the batch's own maximum, its floor)), so
 *                 a caller that knows the corpus's widths gets its arrays
 *                 made once, at their final shape.
 *
 * Returns the flatten_batch result dict plus "genname" (uint8 [N]),
 * "parent_idx" and "ragged_keysets" (extras computed in the same pass),
 * and "fill_bytes": (the prefill bytes the workers wrote inside the
 * released phases, those written with the GIL held).
 */
static PyObject *
py_flatten_json_batch(PyObject *self, PyObject *args)
{
    PyObject *items, *scalars, *axes, *raggeds, *keysets, *mk_axes;
    PyObject *parent_specs, *rk_specs, *canons, *to_id, *to_str;
    PyObject *floors = Py_None;
    Py_ssize_t pad_n;
    long bucket;
    int nthreads;
    (void)self;
    if (!PyArg_ParseTuple(args, "OOOOOOOOOOOnli|O", &items, &scalars, &axes,
                          &raggeds, &keysets, &mk_axes, &parent_specs,
                          &rk_specs, &canons, &to_id, &to_str, &pad_n,
                          &bucket, &nthreads, &floors))
        return NULL;
    if (!PyList_Check(items)) {
        PyErr_SetString(PyExc_TypeError, "items must be a list");
        return NULL;
    }
    Work w;
    memset(&w, 0, sizeof(w));
    Arena spec_arena = {NULL};
    Py_buffer *views = NULL;
    PyObject *result = NULL;

    w.n_real = PyList_GET_SIZE(items);
    w.n_pad = pad_n > w.n_real ? pad_n : w.n_real;
    w.bucket = bucket > 0 ? bucket : 8;
    w.n_scalars = (int)PyList_GET_SIZE(scalars);
    w.n_axes = (int)PyList_GET_SIZE(axes);
    w.n_raggeds = (int)PyList_GET_SIZE(raggeds);
    w.n_keysets = (int)PyList_GET_SIZE(keysets);
    w.n_mk = (int)PyList_GET_SIZE(mk_axes);
    w.n_parents = (int)PyList_GET_SIZE(parent_specs);
    w.n_rks = (int)PyList_GET_SIZE(rk_specs);
    w.n_canons = (int)PyList_GET_SIZE(canons);

    /* buffers (``views`` only once an item is no exact bytes) */
    w.bufs = (const char **)malloc((size_t)(w.n_real ? w.n_real : 1) *
                                   sizeof(char *));
    w.blens = (Py_ssize_t *)malloc((size_t)(w.n_real ? w.n_real : 1) *
                                   sizeof(Py_ssize_t));
    if (!w.bufs || !w.blens)
        goto oom;
    for (Py_ssize_t i = 0; i < w.n_real; i++) {
        PyObject *it = PyList_GET_ITEM(items, i);
        if (PyBytes_CheckExact(it)) {
            /* the overwhelmingly common case: skip the buffer-protocol
             * machinery (the items list keeps the bytes alive) */
            w.bufs[i] = PyBytes_AS_STRING(it);
            w.blens[i] = PyBytes_GET_SIZE(it);
            continue;
        }
        if (views == NULL) {
            views = (Py_buffer *)calloc((size_t)w.n_real, sizeof(Py_buffer));
            if (views == NULL)
                goto oom;
        }
        if (PyObject_GetBuffer(it, &views[i], PyBUF_SIMPLE) < 0)
            goto error;
        w.bufs[i] = (const char *)views[i].buf;
        w.blens[i] = views[i].len;
    }

    /* specs */
#define ALLOCN(ptr, type, count) \
    do { \
        (ptr) = (type *)calloc((size_t)((count) ? (count) : 1), \
                               sizeof(type)); \
        if ((ptr) == NULL) \
            goto oom; \
    } while (0)
    ALLOCN(w.scalars, CPath, w.n_scalars);
    ALLOCN(w.scalar_review, int, w.n_scalars);
    for (int s = 0; s < w.n_scalars; s++) {
        PyObject *tup = PyList_GET_ITEM(scalars, s);
        if (cpath_conv(tup, &w.scalars[s], &spec_arena) < 0)
            goto error;
        w.scalar_review[s] = (w.scalars[s].n > 0 &&
                              w.scalars[s].lens[0] == 10 &&
                              memcmp(w.scalars[s].parts[0], "__review__",
                                     10) == 0);
    }
    ALLOCN(w.axes, CAxis, w.n_axes);
    for (int a = 0; a < w.n_axes; a++) {
        if (caxis_conv(PyList_GET_ITEM(axes, a), &w.axes[a],
                       &spec_arena) < 0)
            goto error;
    }
    ALLOCN(w.raggeds, CRagged, w.n_raggeds);
    for (int r = 0; r < w.n_raggeds; r++) {
        PyObject *e = PyList_GET_ITEM(raggeds, r);
        w.raggeds[r].axis = (int)PyLong_AsLong(PyTuple_GET_ITEM(e, 0));
        if (cpath_conv(PyTuple_GET_ITEM(e, 1), &w.raggeds[r].sub,
                       &spec_arena) < 0)
            goto error;
    }
    ALLOCN(w.keysets, CPath, w.n_keysets);
    for (int s = 0; s < w.n_keysets; s++) {
        if (cpath_conv(PyList_GET_ITEM(keysets, s), &w.keysets[s],
                       &spec_arena) < 0)
            goto error;
    }
    ALLOCN(w.mk_axes, int, w.n_mk);
    for (int q = 0; q < w.n_mk; q++)
        w.mk_axes[q] = (int)PyLong_AsLong(PyList_GET_ITEM(mk_axes, q));
    ALLOCN(w.parents, CParentSpec, w.n_parents);
    for (int p = 0; p < w.n_parents; p++) {
        PyObject *e = PyList_GET_ITEM(parent_specs, p);
        w.parents[p].child = (int)PyLong_AsLong(PyTuple_GET_ITEM(e, 0));
        w.parents[p].parent = (int)PyLong_AsLong(PyTuple_GET_ITEM(e, 1));
    }
    ALLOCN(w.rks, CRKSpec, w.n_rks);
    for (int s = 0; s < w.n_rks; s++) {
        PyObject *e = PyList_GET_ITEM(rk_specs, s);
        w.rks[s].axis = (int)PyLong_AsLong(PyTuple_GET_ITEM(e, 0));
        if (cpath_conv(PyTuple_GET_ITEM(e, 1), &w.rks[s].sub,
                       &spec_arena) < 0)
            goto error;
    }
    ALLOCN(w.canons, CCanonSpec, w.n_canons);
    for (int s = 0; s < w.n_canons; s++) {
        PyObject *e = PyList_GET_ITEM(canons, s);
        if (cpath_conv(PyTuple_GET_ITEM(e, 0), &w.canons[s].path,
                       &spec_arena) < 0)
            goto error;
        w.canons[s].ns_scoped =
            (int)PyLong_AsLong(PyTuple_GET_ITEM(e, 1));
    }
    if (PyErr_Occurred())
        goto error;
    ALLOCN(w.ax_floor, Py_ssize_t, w.n_axes);
    ALLOCN(w.ks_floor, Py_ssize_t, w.n_keysets);
    ALLOCN(w.rk_floor, Py_ssize_t, w.n_rks);
    if (floors != Py_None &&
        (floors_conv(floors, 0, w.ax_floor, w.n_axes) < 0 ||
         floors_conv(floors, 1, w.ks_floor, w.n_keysets) < 0 ||
         floors_conv(floors, 2, w.rk_floor, w.n_rks) < 0))
        goto error;

    /* per-axis ragged extraction plan: self-column lists + subpath
     * tries (see RTrie) */
    ALLOCN(w.ax_trie, RTrie *, w.n_axes);
    ALLOCN(w.ax_self, int *, w.n_axes);
    ALLOCN(w.ax_nself, int, w.n_axes);
    ALLOCN(w.ax_m, Py_ssize_t, w.n_axes);
    for (int r = 0; r < w.n_raggeds; r++)
        if (w.raggeds[r].sub.n == 0)
            w.ax_nself[w.raggeds[r].axis]++;
    for (int a = 0; a < w.n_axes; a++) {
        if (w.ax_nself[a]) {
            w.ax_self[a] = (int *)arena_alloc(
                &spec_arena, (size_t)w.ax_nself[a] * sizeof(int));
            if (w.ax_self[a] == NULL)
                goto oom;
            w.ax_nself[a] = 0; /* refilled below */
        }
    }
    for (int r = 0; r < w.n_raggeds; r++) {
        const CRagged *rg = &w.raggeds[r];
        int a = rg->axis;
        if (rg->sub.n == 0) {
            w.ax_self[a][w.ax_nself[a]++] = r;
            continue;
        }
        RTrie *node = w.ax_trie[a];
        if (node == NULL) {
            node = (RTrie *)arena_alloc(&spec_arena, sizeof(RTrie));
            if (node == NULL)
                goto oom;
            memset(node, 0, sizeof(*node));
            node->col = -1;
            w.ax_trie[a] = node;
        }
        for (int q = 0; q < rg->sub.n; q++) {
            node = rtrie_child(node, rg->sub.parts[q], rg->sub.lens[q],
                               &spec_arena);
            if (node == NULL)
                goto oom;
        }
        node->col = r;
    }
    /* scalar-path trie: non-review scalars share prefix walks too
     * (metadata.* / spec.* fan out from two root lookups) */
    ALLOCN(w.sc_self, int, w.n_scalars);
    for (int s = 0; s < w.n_scalars; s++) {
        if (w.scalar_review[s])
            continue;
        const CPath *sp = &w.scalars[s];
        if (sp->n == 0) {
            w.sc_self[w.sc_nself++] = s;
            continue;
        }
        RTrie *node = w.sc_trie;
        if (node == NULL) {
            node = (RTrie *)arena_alloc(&spec_arena, sizeof(RTrie));
            if (node == NULL)
                goto oom;
            memset(node, 0, sizeof(*node));
            node->col = -1;
            w.sc_trie = node;
        }
        for (int q = 0; q < sp->n; q++) {
            node = rtrie_child(node, sp->parts[q], sp->lens[q],
                               &spec_arena);
            if (node == NULL)
                goto oom;
        }
        node->col = s;
    }

    /* rows (block-allocated sub-arrays; worker_rows zeroes and wires
     * them) */
    if (w.n_real > 0) {
        size_t n = (size_t)w.n_real;
        w.rows = (Row *)malloc(n * sizeof(Row));
        w.ax_blk = (AxisItems *)malloc(
            n * (size_t)(w.n_axes ? w.n_axes : 1) * sizeof(AxisItems));
        w.ks_blk = (KeysetRow *)malloc(
            n * (size_t)(w.n_keysets ? w.n_keysets : 1) * sizeof(KeysetRow));
        w.rk_blk = (RKRow *)malloc(
            n * (size_t)(w.n_rks ? w.n_rks : 1) * sizeof(RKRow));
        if (!w.rows || !w.ax_blk || !w.ks_blk || !w.rk_blk)
            goto oom;
    }

    /* threads */
    if (nthreads < 1)
        nthreads = 1;
    if (nthreads > 64)
        nthreads = 64;
    {
        long by_rows = (long)(w.n_real / 128) + 1;
        if ((long)nthreads > by_rows)
            nthreads = (int)by_rows;
    }
    w.nthreads = nthreads;
    ALLOCN(w.tc, ThreadCtx, w.nthreads);
    {
        Py_ssize_t block = w.nthreads
            ? (w.n_real + w.nthreads - 1) / w.nthreads : 0;
        for (int t = 0; t < w.nthreads; t++) {
            ThreadCtx *tc = &w.tc[t];
            tc->w = &w;
            tc->tid = t;
            tc->row0 = (Py_ssize_t)t * block;
            tc->row1 = tc->row0 + block;
            if (tc->row0 > w.n_real)
                tc->row0 = w.n_real;
            if (tc->row1 > w.n_real)
                tc->row1 = w.n_real;
            tc->cc = ctx_acquire();
            if (tc->cc == NULL)
                goto oom;
            tc->arena = tc->cc->arena;
            tc->intern = tc->cc->intern;
            tc->parser.nstack = tc->cc->nstack;
            tc->parser.kstack = tc->cc->kstack;
            tc->parser.lstack = tc->cc->lstack;
            tc->parser.scap = tc->cc->scap;
            tc->sa = tc->cc->sa;
            tc->sb = tc->cc->sb;
            tc->sout = tc->cc->sout;
            ALLOCN(tc->max_axis, Py_ssize_t, w.n_axes);
            ALLOCN(tc->max_keyset, Py_ssize_t, w.n_keysets);
            ALLOCN(tc->max_rk_l, Py_ssize_t, w.n_rks);
        }
    }

    /* phase-1 output arrays + result containers */
    result = PyDict_New();
    if (result == NULL)
        goto error;
    {
        npy_intp d1[1] = {(npy_intp)w.n_pad};
        PyArrayObject *gid = new_arr(&w.fill1, 1, d1, NPY_INT32, -1);
        PyArrayObject *kid = new_arr(&w.fill1, 1, d1, NPY_INT32, -1);
        PyArrayObject *nsid = new_arr(&w.fill1, 1, d1, NPY_INT32, -1);
        PyArrayObject *nmid = new_arr(&w.fill1, 1, d1, NPY_INT32, -1);
        PyArrayObject *gen = new_arr(&w.fill1, 1, d1, NPY_UINT8, 0);
        if (!gid || !kid || !nsid || !nmid || !gen) {
            Py_XDECREF(gid); Py_XDECREF(kid); Py_XDECREF(nsid);
            Py_XDECREF(nmid); Py_XDECREF(gen);
            goto error;
        }
        w.gid = (int32_t *)PyArray_DATA(gid);
        w.kid = (int32_t *)PyArray_DATA(kid);
        w.nsid = (int32_t *)PyArray_DATA(nsid);
        w.nmid = (int32_t *)PyArray_DATA(nmid);
        w.genname = (uint8_t *)PyArray_DATA(gen);
        PyObject *identity = Py_BuildValue("(NNNNN)", gid, kid, nsid, nmid,
                                           gen);
        if (identity == NULL ||
            PyDict_SetItemString(result, "identity", identity) < 0) {
            Py_XDECREF(identity);
            goto error;
        }
        Py_DECREF(identity);

        ALLOCN(w.s_kind, signed char *, w.n_scalars);
        ALLOCN(w.s_num, float *, w.n_scalars);
        ALLOCN(w.s_sid, int32_t *, w.n_scalars);
        PyObject *s_out = PyList_New(w.n_scalars);
        if (s_out == NULL)
            goto error;
        for (int s = 0; s < w.n_scalars; s++) {
            PyArrayObject *a_kind = new_arr(&w.fill1, 1, d1, NPY_INT8, 0);
            PyArrayObject *a_num = new_arr(&w.fill1, 1, d1, NPY_FLOAT32, 0);
            PyArrayObject *a_sid = new_arr(&w.fill1, 1, d1, NPY_INT32, -1);
            if (!a_kind || !a_num || !a_sid) {
                Py_XDECREF(a_kind); Py_XDECREF(a_num); Py_XDECREF(a_sid);
                Py_DECREF(s_out);
                goto error;
            }
            w.s_kind[s] = (signed char *)PyArray_DATA(a_kind);
            w.s_num[s] = (float *)PyArray_DATA(a_num);
            w.s_sid[s] = (int32_t *)PyArray_DATA(a_sid);
            PyList_SET_ITEM(s_out, s, Py_BuildValue("(NNN)", a_kind, a_num,
                                                    a_sid));
        }
        if (PyDict_SetItemString(result, "scalars", s_out) < 0) {
            Py_DECREF(s_out);
            goto error;
        }
        Py_DECREF(s_out);

        ALLOCN(w.c_sid, int32_t *, w.n_canons);
        PyObject *c_out = PyList_New(w.n_canons);
        if (c_out == NULL)
            goto error;
        for (int s = 0; s < w.n_canons; s++) {
            PyArrayObject *a_sid = new_arr(&w.fill1, 1, d1, NPY_INT32, -2);
            if (a_sid == NULL) {
                Py_DECREF(c_out);
                goto error;
            }
            w.c_sid[s] = (int32_t *)PyArray_DATA(a_sid);
            PyList_SET_ITEM(c_out, s, (PyObject *)a_sid);
        }
        if (PyDict_SetItemString(result, "canons", c_out) < 0) {
            Py_DECREF(c_out);
            goto error;
        }
        Py_DECREF(c_out);

        ALLOCN(w.a_count, int32_t *, w.n_axes);
        PyObject *a_out = PyList_New(w.n_axes);
        if (a_out == NULL)
            goto error;
        for (int a = 0; a < w.n_axes; a++) {
            PyArrayObject *cnt = new_arr(&w.fill1, 1, d1, NPY_INT32, 0);
            if (cnt == NULL) {
                Py_DECREF(a_out);
                goto error;
            }
            w.a_count[a] = (int32_t *)PyArray_DATA(cnt);
            PyList_SET_ITEM(a_out, a, (PyObject *)cnt);
        }
        if (PyDict_SetItemString(result, "axes", a_out) < 0) {
            Py_DECREF(a_out);
            goto error;
        }
        Py_DECREF(a_out);
    }

    /* phase 1: parse + fixed-dim columns (GIL released) */
    run_phase_released(&w, 1);
    for (int t = 0; t < w.nthreads; t++) {
        if (w.tc[t].err == 1)
            goto oom;
        if (w.tc[t].err == 2) {
            PyErr_Format(PyExc_ValueError,
                         "invalid JSON in batch item %zd",
                         (Py_ssize_t)w.tc[t].err_row);
            goto error;
        }
    }

    /* widths from thread-local maxima and the caller's floors, then
     * phase-2 arrays */
    {
        npy_intp d1[1] = {(npy_intp)w.n_pad};
        ALLOCN(w.r_kind, signed char *, w.n_raggeds);
        ALLOCN(w.r_num, float *, w.n_raggeds);
        ALLOCN(w.r_sid, int32_t *, w.n_raggeds);
        ALLOCN(w.r_m, Py_ssize_t, w.n_raggeds);
        PyObject *r_out = PyList_New(w.n_raggeds);
        if (r_out == NULL)
            goto error;
        for (int r = 0; r < w.n_raggeds; r++) {
            Py_ssize_t maxc = w.ax_floor[w.raggeds[r].axis];
            for (int t = 0; t < w.nthreads; t++)
                if (w.tc[t].max_axis[w.raggeds[r].axis] > maxc)
                    maxc = w.tc[t].max_axis[w.raggeds[r].axis];
            Py_ssize_t m = bucket_up((long)maxc, w.bucket);
            w.r_m[r] = m;
            w.ax_m[w.raggeds[r].axis] = m;
            npy_intp d2[2] = {(npy_intp)w.n_pad, (npy_intp)m};
            PyArrayObject *a_kind = new_arr(&w.fill2, 2, d2, NPY_INT8, 0);
            PyArrayObject *a_num = new_arr(&w.fill2, 2, d2, NPY_FLOAT32, 0);
            PyArrayObject *a_sid = new_arr(&w.fill2, 2, d2, NPY_INT32, -1);
            if (!a_kind || !a_num || !a_sid) {
                Py_XDECREF(a_kind); Py_XDECREF(a_num); Py_XDECREF(a_sid);
                Py_DECREF(r_out);
                goto error;
            }
            w.r_kind[r] = (signed char *)PyArray_DATA(a_kind);
            w.r_num[r] = (float *)PyArray_DATA(a_num);
            w.r_sid[r] = (int32_t *)PyArray_DATA(a_sid);
            PyList_SET_ITEM(r_out, r, Py_BuildValue("(NNN)", a_kind, a_num,
                                                    a_sid));
        }
        if (PyDict_SetItemString(result, "raggeds", r_out) < 0) {
            Py_DECREF(r_out);
            goto error;
        }
        Py_DECREF(r_out);

        ALLOCN(w.k_sid, int32_t *, w.n_keysets);
        ALLOCN(w.k_cnt, int32_t *, w.n_keysets);
        ALLOCN(w.k_l, Py_ssize_t, w.n_keysets);
        PyObject *k_out = PyList_New(w.n_keysets);
        if (k_out == NULL)
            goto error;
        for (int s = 0; s < w.n_keysets; s++) {
            Py_ssize_t maxc = w.ks_floor[s];
            for (int t = 0; t < w.nthreads; t++)
                if (w.tc[t].max_keyset[s] > maxc)
                    maxc = w.tc[t].max_keyset[s];
            Py_ssize_t l = bucket_up((long)maxc, w.bucket);
            w.k_l[s] = l;
            npy_intp d2[2] = {(npy_intp)w.n_pad, (npy_intp)l};
            PyArrayObject *a_sid = new_arr(&w.fill2, 2, d2, NPY_INT32, -1);
            PyArrayObject *a_cnt = new_arr(&w.fill2, 1, d1, NPY_INT32, 0);
            if (!a_sid || !a_cnt) {
                Py_XDECREF(a_sid); Py_XDECREF(a_cnt); Py_DECREF(k_out);
                goto error;
            }
            w.k_sid[s] = (int32_t *)PyArray_DATA(a_sid);
            w.k_cnt[s] = (int32_t *)PyArray_DATA(a_cnt);
            PyList_SET_ITEM(k_out, s, Py_BuildValue("(NN)", a_sid, a_cnt));
        }
        if (PyDict_SetItemString(result, "keysets", k_out) < 0) {
            Py_DECREF(k_out);
            goto error;
        }
        Py_DECREF(k_out);

        ALLOCN(w.mk_sid, int32_t *, w.n_mk);
        ALLOCN(w.mk_m, Py_ssize_t, w.n_mk);
        PyObject *mk_out = PyList_New(w.n_mk);
        if (mk_out == NULL)
            goto error;
        for (int q = 0; q < w.n_mk; q++) {
            Py_ssize_t maxc = w.ax_floor[w.mk_axes[q]];
            for (int t = 0; t < w.nthreads; t++)
                if (w.tc[t].max_axis[w.mk_axes[q]] > maxc)
                    maxc = w.tc[t].max_axis[w.mk_axes[q]];
            Py_ssize_t m = bucket_up((long)maxc, w.bucket);
            w.mk_m[q] = m;
            npy_intp d2[2] = {(npy_intp)w.n_pad, (npy_intp)m};
            PyArrayObject *a_sid = new_arr(&w.fill2, 2, d2, NPY_INT32, -1);
            if (a_sid == NULL) {
                Py_DECREF(mk_out);
                goto error;
            }
            w.mk_sid[q] = (int32_t *)PyArray_DATA(a_sid);
            PyList_SET_ITEM(mk_out, q, (PyObject *)a_sid);
        }
        if (PyDict_SetItemString(result, "map_keys", mk_out) < 0) {
            Py_DECREF(mk_out);
            goto error;
        }
        Py_DECREF(mk_out);

        ALLOCN(w.p_idx, int32_t *, w.n_parents);
        ALLOCN(w.p_m, Py_ssize_t, w.n_parents);
        PyObject *p_out = PyList_New(w.n_parents);
        if (p_out == NULL)
            goto error;
        for (int p = 0; p < w.n_parents; p++) {
            Py_ssize_t maxc = w.ax_floor[w.parents[p].child];
            for (int t = 0; t < w.nthreads; t++)
                if (w.tc[t].max_axis[w.parents[p].child] > maxc)
                    maxc = w.tc[t].max_axis[w.parents[p].child];
            Py_ssize_t m = bucket_up((long)maxc, w.bucket);
            w.p_m[p] = m;
            npy_intp d2[2] = {(npy_intp)w.n_pad, (npy_intp)m};
            PyArrayObject *a_idx = new_arr(&w.fill2, 2, d2, NPY_INT32, -1);
            if (a_idx == NULL) {
                Py_DECREF(p_out);
                goto error;
            }
            w.p_idx[p] = (int32_t *)PyArray_DATA(a_idx);
            PyList_SET_ITEM(p_out, p, (PyObject *)a_idx);
        }
        if (PyDict_SetItemString(result, "parent_idx", p_out) < 0) {
            Py_DECREF(p_out);
            goto error;
        }
        Py_DECREF(p_out);

        ALLOCN(w.rk_sid, int32_t *, w.n_rks);
        ALLOCN(w.rk_cnt, int32_t *, w.n_rks);
        ALLOCN(w.rk_m, Py_ssize_t, w.n_rks);
        ALLOCN(w.rk_l, Py_ssize_t, w.n_rks);
        PyObject *rk_out = PyList_New(w.n_rks);
        if (rk_out == NULL)
            goto error;
        for (int s = 0; s < w.n_rks; s++) {
            Py_ssize_t maxm = w.ax_floor[w.rks[s].axis];
            Py_ssize_t maxl = w.rk_floor[s];
            for (int t = 0; t < w.nthreads; t++) {
                if (w.tc[t].max_axis[w.rks[s].axis] > maxm)
                    maxm = w.tc[t].max_axis[w.rks[s].axis];
                if (w.tc[t].max_rk_l[s] > maxl)
                    maxl = w.tc[t].max_rk_l[s];
            }
            Py_ssize_t m = bucket_up((long)maxm, w.bucket);
            Py_ssize_t l = bucket_up((long)maxl, w.bucket);
            w.rk_m[s] = m;
            w.rk_l[s] = l;
            npy_intp d3[3] = {(npy_intp)w.n_pad, (npy_intp)m, (npy_intp)l};
            npy_intp d2[2] = {(npy_intp)w.n_pad, (npy_intp)m};
            PyArrayObject *a_sid = new_arr(&w.fill2, 3, d3, NPY_INT32, -1);
            PyArrayObject *a_cnt = new_arr(&w.fill2, 2, d2, NPY_INT32, 0);
            if (!a_sid || !a_cnt) {
                Py_XDECREF(a_sid); Py_XDECREF(a_cnt); Py_DECREF(rk_out);
                goto error;
            }
            w.rk_sid[s] = (int32_t *)PyArray_DATA(a_sid);
            w.rk_cnt[s] = (int32_t *)PyArray_DATA(a_cnt);
            PyList_SET_ITEM(rk_out, s, Py_BuildValue("(NN)", a_sid, a_cnt));
        }
        if (PyDict_SetItemString(result, "ragged_keysets", rk_out) < 0) {
            Py_DECREF(rk_out);
            goto error;
        }
        Py_DECREF(rk_out);
    }

    /* phase 2: variable-width columns (GIL released) */
    run_phase_released(&w, 2);
    for (int t = 0; t < w.nthreads; t++)
        if (w.tc[t].err == 1)
            goto oom;

    /* merge per-thread interns into the Python vocab (deterministic:
     * thread order, then first-seen order).  The persistent mirror
     * resolves every already-known string with one C hash probe; only
     * genuinely new strings create Python objects — a chunked sweep
     * used to re-pay a decode + dict lookup per string per chunk. */
    {
        int vm_ok;
        {
            int r = vm_sync(to_id, to_str);
            if (r < 0)
                goto oom;
            vm_ok = (r == 0);
        }
        for (int t = 0; t < w.nthreads; t++) {
            ThreadCtx *tc = &w.tc[t];
            if (tc->intern.count == 0)
                continue;
            tc->remap = (int32_t *)malloc(tc->intern.count *
                                          sizeof(int32_t));
            if (tc->remap == NULL)
                goto oom;
            for (uint32_t id = 0; id < tc->intern.count; id++) {
                if (vm_ok) {
                    int32_t mhit = intern_lookup(&g_vm.table,
                                                 tc->intern.strs[id],
                                                 tc->intern.lens[id]);
                    if (mhit >= 0) {
                        tc->remap[id] = mhit;
                        continue;
                    }
                }
                PyObject *key = PyUnicode_DecodeUTF8(
                    tc->intern.strs[id], (Py_ssize_t)tc->intern.lens[id],
                    "strict");
                if (key == NULL)
                    goto error;
                PyObject *hit = PyDict_GetItem(to_id, key);
                long gl;
                if (hit != NULL) {
                    gl = PyLong_AsLong(hit);
                } else {
                    gl = (long)PyList_GET_SIZE(to_str);
                    PyObject *idobj = PyLong_FromLong(gl);
                    if (idobj == NULL ||
                        PyDict_SetItem(to_id, key, idobj) < 0 ||
                        PyList_Append(to_str, key) < 0) {
                        Py_XDECREF(idobj);
                        Py_DECREF(key);
                        goto error;
                    }
                    Py_DECREF(idobj);
                    /* cache the new entry; the position guard covers
                     * vocab writes interleaved by GC callbacks (the
                     * mirror only ever stores verified positions) */
                    if (vm_ok && gl == (long)g_vm.count &&
                        vm_push(key) < 0) {
                        Py_DECREF(key);
                        goto oom;
                    }
                }
                Py_DECREF(key);
                tc->remap[id] = (int32_t)gl;
            }
        }
    }

    /* phase 3: remap local sids -> global (GIL released) */
    run_phase_released(&w, 3);

    {
        size_t filled = 0;
        for (int t = 0; t < w.nthreads; t++)
            filled += w.tc[t].filled;
        PyObject *fb = Py_BuildValue("(nn)", (Py_ssize_t)filled,
                                     (Py_ssize_t)(w.fill1.held +
                                                  w.fill2.held));
        if (fb == NULL || PyDict_SetItemString(result, "fill_bytes", fb) < 0) {
            Py_XDECREF(fb);
            goto error;
        }
        Py_DECREF(fb);
    }

    work_free(&w, views, w.n_real, &spec_arena);
    return result;

oom:
    PyErr_NoMemory();
error:
    work_free(&w, views, w.n_real, &spec_arena);
    Py_XDECREF(result);
    return NULL;
}

/* released_cpu() -> CPU seconds the calling thread has spent in the three
 * phases with the GIL released, since it first called in */
static PyObject *
released_cpu(PyObject *self, PyObject *noargs)
{
    (void)self;
    (void)noargs;
    return PyFloat_FromDouble(released_s);
}

static PyMethodDef jmethods[] = {
    {"flatten_json_batch", py_flatten_json_batch, METH_VARARGS,
     "Flatten a batch of raw JSON documents into columnar arrays "
     "(threaded, GIL-released)."},
    {"released_cpu", released_cpu, METH_NOARGS,
     "CPU seconds of the calling thread inside the columnizer's phases "
     "with the GIL released."},
    {NULL, NULL, 0, NULL},
};

static void
jmodule_free(void *mod)
{
    (void)mod;
    while (g_ctx_pool != NULL) {
        CtxCache *c = g_ctx_pool;
        g_ctx_pool = c->next;
        ctx_destroy(c);
    }
    g_ctx_pool_n = 0;
    if (g_vm.inited) {
        for (Py_ssize_t i = 0; i < g_vm.count; i++)
            Py_DECREF(g_vm.objs[i]);
        free((void *)g_vm.objs);
        intern_destroy(&g_vm.table);
        memset(&g_vm, 0, sizeof(g_vm));
    }
}

static struct PyModuleDef jmoduledef = {
    PyModuleDef_HEAD_INIT, "gtpu_flattenjson", NULL, -1, jmethods,
    NULL, NULL, NULL, jmodule_free,
};

PyMODINIT_FUNC
PyInit_gtpu_flattenjson(void)
{
    import_array();
    return PyModule_Create(&jmoduledef);
}
