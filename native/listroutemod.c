/* gtpu_listroute: the audit lister's per-object routing, in one call.
 *
 * The pass's calling thread pulls every listed object, reads its kind,
 * looks its kind group up and appends it to that group's chunk buffer
 * (gatekeeper_tpu/ops/listroute.py has the per-object Python loop this
 * replaces; that loop stays the reference and the fallback, and
 * tests/test_list_routing.py holds the two to the same chunk sequence).
 * route() does that for object after object, with no Python frame of the
 * program's in between, until a buffer is full, the lister ends or it
 * raises.
 *
 * What the head scan settles by itself: an unloaded RawJSON (exact
 * class, _loaded False, raw a bytes) whose bytes open with
 *     {"apiVersion":"...","kind":"..."     or     {"kind":"..."
 * the two forms utils/rawjson._HEAD_KIND accepts, with no quote and no
 * backslash inside either value, and a kind that is UTF-8.  Everything
 * else goes through peek_kind, object by object, and is counted.
 *
 * What it takes off the cyclic collector's lists on the way: every such
 * unloaded RawJSON whose dict is still empty.  It refers to one bytes and
 * one bool and so can be part of no cycle, yet as an instance of a dict
 * subclass it is tracked from birth, and a pass's worth of them, each
 * alive as long as its chunk, is what promoted into CPython's full
 * collections.  utils/rawjson puts the object back through track() the
 * moment it loads, before a container can go into it.
 *
 * What it reads besides the head: identity() gives the audit fold the four
 * strings a kept violation names its object by (apiVersion, kind,
 * metadata.name, metadata.namespace) from the bytes of such an unloaded
 * RawJSON, in one validating pass over the whole top-level object (keys
 * may repeat, the last wins, and metadata need not come early), so that
 * the fold loads no object for them.  It answers None wherever json.loads
 * and the dict would, or might, say anything else.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <structmember.h>
#include <string.h>

static PyTypeObject *raw_type = NULL; /* utils/rawjson.RawJSON */
static Py_ssize_t off_raw, off_loaded; /* its two slots */

/* offset of a __slots__ member of a heap type, -1 with an error set */
static Py_ssize_t
slot_offset(PyTypeObject *tp, const char *name)
{
    PyObject *d = PyObject_GetAttrString((PyObject *)tp, name);
    if (d == NULL)
        return -1;
    Py_ssize_t off = -1;
    if (Py_TYPE(d) == &PyMemberDescr_Type) {
        PyMemberDef *m = ((PyMemberDescrObject *)d)->d_member;
        if (m->type == T_OBJECT_EX && m->offset > 0)
            off = m->offset;
    }
    Py_DECREF(d);
    if (off < 0)
        PyErr_Format(PyExc_TypeError, "%s.%s is not a slot",
                     tp->tp_name, name);
    return off;
}

static PyObject *
bind(PyObject *self, PyObject *arg)
{
    (void)self;
    if (!PyType_Check(arg)) {
        PyErr_SetString(PyExc_TypeError, "bind() takes the RawJSON class");
        return NULL;
    }
    PyTypeObject *tp = (PyTypeObject *)arg;
    Py_ssize_t r = slot_offset(tp, "raw");
    Py_ssize_t l = r < 0 ? -1 : slot_offset(tp, "_loaded");
    if (l < 0)
        return NULL;
    Py_INCREF(tp);
    Py_XSETREF(raw_type, tp);
    off_raw = r;
    off_loaded = l;
    Py_RETURN_NONE;
}

/* The kind in the head of p[0:n], or 0: what the anchored regex
 *   ^\{"(?:apiVersion":"[^"\\]*",")?kind":"([^"\\]*)"
 * of utils/rawjson matches, byte for byte. */
static int
head_kind(const char *p, Py_ssize_t n, const char **kind, Py_ssize_t *len)
{
    static const char K[] = "{\"kind\":\"";
    static const char A[] = "{\"apiVersion\":\"";
    static const char AK[] = "\",\"kind\":\"";
    const Py_ssize_t nk = sizeof(K) - 1, na = sizeof(A) - 1,
                     nak = sizeof(AK) - 1;
    Py_ssize_t i;
    if (n >= nk && memcmp(p, K, nk) == 0) {
        i = nk;
    } else if (n >= na && memcmp(p, A, na) == 0) {
        i = na;
        while (i < n && p[i] != '"' && p[i] != '\\')
            i++;
        if (n - i < nak || memcmp(p + i, AK, nak) != 0)
            return 0;
        i += nak;
    } else {
        return 0;
    }
    Py_ssize_t start = i;
    while (i < n && p[i] != '"' && p[i] != '\\')
        i++;
    if (i >= n || p[i] != '"')
        return 0;
    *kind = p + start;
    *len = i - start;
    return 1;
}

static PyObject *
py_head_kind(PyObject *self, PyObject *arg)
{
    (void)self;
    const char *kind;
    Py_ssize_t len;
    if (!PyBytes_Check(arg)) {
        PyErr_SetString(PyExc_TypeError, "head_kind() takes bytes");
        return NULL;
    }
    if (!head_kind(PyBytes_AS_STRING(arg), PyBytes_GET_SIZE(arg),
                   &kind, &len))
        Py_RETURN_NONE;
    return PyBytes_FromStringAndSize(kind, len);
}

/* The entry of an unloaded RawJSON's kind, read from the head of its
 * bytes: a new reference, or NULL with *err 0 where the head settles
 * nothing (the caller asks peek_kind) and *err 1 with an error set.
 * kinds maps kind bytes to entries and is filled here, once per kind.
 * An object that is unloaded, holds a bytes and has an empty dict leaves
 * the collector's lists here, whether or not its head then settles its
 * kind, and *untracked grows by one. */
static PyObject *
head_entry(PyObject *obj, PyObject *kinds, PyObject *entry_of, int *err,
           Py_ssize_t *untracked)
{
    *err = 0;
    PyObject *loaded = *(PyObject **)((char *)obj + off_loaded);
    PyObject *raw = *(PyObject **)((char *)obj + off_raw);
    const char *kind;
    Py_ssize_t len;
    if (loaded != Py_False || raw == NULL || !PyBytes_CheckExact(raw))
        return NULL;
    if (PyDict_GET_SIZE(obj) == 0) {
        PyObject_GC_UnTrack(obj); /* no effect on one already off */
        ++*untracked;
    }
    if (!head_kind(PyBytes_AS_STRING(raw), PyBytes_GET_SIZE(raw),
                   &kind, &len))
        return NULL;
    *err = 1;
    PyObject *key = PyBytes_FromStringAndSize(kind, len);
    if (key == NULL)
        return NULL;
    PyObject *entry = PyDict_GetItemWithError(kinds, key); /* borrowed */
    if (entry != NULL) {
        Py_INCREF(entry);
    } else if (!PyErr_Occurred()) {
        PyObject *name = PyUnicode_DecodeUTF8(kind, len, NULL);
        if (name == NULL) {
            if (PyErr_ExceptionMatches(PyExc_UnicodeDecodeError)) {
                PyErr_Clear(); /* peek_kind's answer, not ours */
                *err = 0;
            }
        } else {
            entry = PyObject_CallOneArg(entry_of, name);
            Py_DECREF(name);
            if (entry != NULL && PyDict_SetItem(kinds, key, entry) < 0)
                Py_CLEAR(entry);
        }
    }
    Py_DECREF(key);
    if (entry != NULL)
        *err = 0;
    return entry;
}

/* lst[i] += n, with whatever exception is set kept as it is */
static void
add_to(PyObject *lst, Py_ssize_t i, Py_ssize_t n)
{
    if (n == 0 || i >= PyList_GET_SIZE(lst))
        return;
    PyObject *exc = PyErr_GetRaisedException();
    PyObject *add = PyLong_FromSsize_t(n);
    PyObject *sum = add == NULL ? NULL
        : PyNumber_Add(PyList_GET_ITEM(lst, i), add);
    Py_XDECREF(add);
    if (sum == NULL || PyList_SetItem(lst, i, sum) < 0)
        PyErr_Clear();
    if (exc != NULL)
        PyErr_SetRaisedException(exc);
}

/* route(it, bufs, kinds, chunk_size, peek_kind, entry_of, counter, counts)
 *
 * Pull objects off the iterator `it` and append each to bufs[entry],
 * where entry = entry_of(kind): None drops the object uncounted (the kind
 * filter), an empty group drops it counted (no template reaches its
 * kind), anything else is the key of its chunk buffer in `bufs` (made on
 * first use, so bufs keeps the order in which groups were first seen).
 * Returns the group whose buffer reached chunk_size, or None when the
 * iterator is exhausted.  An exception of the iterator, of peek_kind or
 * of entry_of passes through, with bufs as they stood.  On every way out
 * counter[0] grows by the objects counted, counts[0] by those the head
 * scan settled, counts[1] by those handed to peek_kind and counts[2] by
 * those taken off the collector's lists. */
static PyObject *
route(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *it, *bufs, *kinds, *peek, *entry_of, *counter, *counts;
    Py_ssize_t chunk;
    if (!PyArg_ParseTuple(args, "OO!O!nOOO!O!", &it, &PyDict_Type, &bufs,
                          &PyDict_Type, &kinds, &chunk, &peek, &entry_of,
                          &PyList_Type, &counter, &PyList_Type, &counts))
        return NULL;
    if (raw_type == NULL) {
        PyErr_SetString(PyExc_RuntimeError, "bind() was not called");
        return NULL;
    }
    if (!PyIter_Check(it)) {
        PyErr_SetString(PyExc_TypeError, "route() takes an iterator");
        return NULL;
    }
    Py_ssize_t listed = 0, fast = 0, slow = 0, untracked = 0;
    PyObject *full = NULL, *obj = NULL, *entry = NULL;
    int failed = 0;
    while (full == NULL) {
        obj = PyIter_Next(it);
        if (obj == NULL) {
            failed = PyErr_Occurred() != NULL;
            break;
        }
        failed = 1;
        entry = NULL;
        if (Py_TYPE(obj) == raw_type) {
            int err;
            entry = head_entry(obj, kinds, entry_of, &err, &untracked);
            if (err)
                break;
        }
        if (entry != NULL) {
            fast++;
        } else {
            slow++;
            PyObject *name = PyObject_CallOneArg(peek, obj);
            if (name == NULL)
                break;
            entry = PyObject_CallOneArg(entry_of, name);
            Py_DECREF(name);
            if (entry == NULL)
                break;
        }
        if (entry != Py_None) {
            int reached = PyObject_IsTrue(entry);
            if (reached < 0)
                break;
            listed++;
            if (reached) {
                PyObject *buf = PyDict_GetItemWithError(bufs, entry);
                if (buf == NULL) {
                    if (PyErr_Occurred())
                        break;
                    buf = PyList_New(0);
                    if (buf == NULL)
                        break;
                    int rc = PyDict_SetItem(bufs, entry, buf);
                    Py_DECREF(buf); /* bufs holds it */
                    if (rc < 0)
                        break;
                } else if (!PyList_Check(buf)) {
                    PyErr_SetString(PyExc_TypeError,
                                    "a chunk buffer is not a list");
                    break;
                }
                if (PyList_Append(buf, obj) < 0)
                    break;
                if (PyList_GET_SIZE(buf) >= chunk) {
                    full = entry;
                    Py_INCREF(full);
                }
            }
        }
        failed = 0;
        Py_CLEAR(entry);
        Py_CLEAR(obj);
    }
    Py_XDECREF(entry);
    Py_XDECREF(obj);
    add_to(counter, 0, listed);
    add_to(counts, 0, fast);
    add_to(counts, 1, slow);
    add_to(counts, 2, untracked);
    if (failed) {
        Py_XDECREF(full);
        return NULL;
    }
    if (full == NULL)
        Py_RETURN_NONE;
    return full;
}

/* --- identity(): a kept violation's four strings, off the bytes --------
 *
 * One pass over the document with a JSON skipper that accepts no more
 * than json.loads does: strings with their escapes checked and no raw
 * control byte, numbers by the grammar, true / false / null, objects and
 * arrays with their commas and colons, the four whitespace bytes between
 * tokens.  Anything else ends the scan and the answer is None. */

#define ID_MAX_DEPTH 64  /* deeper than any object the API serves: None */
#define ID_MAX_NUMBER 64 /* int() refuses 4300 digits; none comes near */

typedef struct {
    const unsigned char *end;
    int high; /* a byte >= 0x80 was seen: the document gets decoded whole */
} id_scan;

/* one of the four values: 0 absent or null (""), 1 a plain string, 2 a
 * value the dict would have to answer for */
typedef struct {
    int state;
    const unsigned char *s;
    Py_ssize_t n;
} id_field;

static const unsigned char *
id_ws(const unsigned char *p, const unsigned char *end)
{
    while (p < end && (*p == ' ' || *p == '\n' || *p == '\r' || *p == '\t'))
        p++;
    return p;
}

/* p at the opening quote: the byte after the closing one, or NULL.
 * *esc is set where the string holds a backslash.  The run of plain bytes
 * is walked without a bound: the document is a bytes object, which ends
 * in a NUL of its own, and a NUL stops the walk like any control byte. */
static const unsigned char *
id_string(id_scan *sc, const unsigned char *p, int *esc)
{
    const unsigned char *end = sc->end;
    *esc = 0;
    for (p++;; p++) {
        while (*p >= 0x20 && *p < 0x80 && *p != '"' && *p != '\\')
            p++;
        if (p >= end)
            return NULL;
        unsigned char c = *p;
        if (c == '"')
            return p + 1;
        if (c < 0x20)
            return NULL;
        if (c >= 0x80) {
            sc->high = 1;
            continue;
        }
        /* a backslash */
        *esc = 1;
        if (++p >= end)
            return NULL;
        if (*p == 'u') {
            if (end - p < 5)
                return NULL;
            for (int i = 1; i <= 4; i++) {
                unsigned char h = p[i];
                if (!((h >= '0' && h <= '9') || (h >= 'a' && h <= 'f')
                      || (h >= 'A' && h <= 'F')))
                    return NULL;
            }
            p += 4;
        } else if (*p == 0 || strchr("\"\\/bfnrt", *p) == NULL) {
            return NULL;
        }
    }
}

static const unsigned char *
id_digits(const unsigned char *p, const unsigned char *end)
{
    const unsigned char *q = p;
    while (q < end && *q >= '0' && *q <= '9')
        q++;
    return q == p ? NULL : q;
}

/* -?(0|[1-9][0-9]*)(\.[0-9]+)?([eE][+-]?[0-9]+)? */
static const unsigned char *
id_number(const unsigned char *p, const unsigned char *end)
{
    const unsigned char *start = p;
    if (p < end && *p == '-')
        p++;
    if (p < end && *p == '0')
        p++;
    else if ((p = id_digits(p, end)) == NULL)
        return NULL;
    if (p < end && *p == '.' && (p = id_digits(p + 1, end)) == NULL)
        return NULL;
    if (p < end && (*p == 'e' || *p == 'E')) {
        p++;
        if (p < end && (*p == '+' || *p == '-'))
            p++;
        if ((p = id_digits(p, end)) == NULL)
            return NULL;
    }
    return p - start > ID_MAX_NUMBER ? NULL : p;
}

static const unsigned char *
id_literal(const unsigned char *p, const unsigned char *end, const char *w)
{
    size_t n = strlen(w);
    if ((size_t)(end - p) < n || memcmp(p, w, n) != 0)
        return NULL;
    return p + n;
}

/* Skip one value of any kind: the byte after it, or NULL.  Containers are
 * walked with their kinds on a small stack, so the C stack stays flat. */
static const unsigned char *
id_skip(id_scan *sc, const unsigned char *p)
{
    const unsigned char *end = sc->end;
    char stack[ID_MAX_DEPTH]; /* '{' or '[' per open container */
    int depth = 0, esc;
    for (;;) {
        /* a value starts at p */
        if (p >= end)
            return NULL;
        switch (*p) {
        case '"':
            p = id_string(sc, p, &esc);
            break;
        case '{':
        case '[':
            if (depth == ID_MAX_DEPTH)
                return NULL;
            stack[depth++] = (char)*p;
            p = id_ws(p + 1, end);
            if (p < end && *p == (stack[depth - 1] == '{' ? '}' : ']')) {
                depth--;
                p++;
                break; /* an empty container is a finished value */
            }
            if (stack[depth - 1] == '[')
                continue;
            goto key;
        case 't':
            p = id_literal(p, end, "true");
            break;
        case 'f':
            p = id_literal(p, end, "false");
            break;
        case 'n':
            p = id_literal(p, end, "null");
            break;
        default:
            p = id_number(p, end);
        }
        /* a value ended at p: close what it completes */
        for (;;) {
            if (p == NULL)
                return NULL;
            if (depth == 0)
                return p;
            p = id_ws(p, end);
            if (p >= end)
                return NULL;
            if (*p == ',') {
                p = id_ws(p + 1, end);
                break;
            }
            if (*p != (stack[depth - 1] == '{' ? '}' : ']'))
                return NULL;
            depth--;
            p++;
        }
        if (stack[depth - 1] == '[')
            continue;
    key:
        if (p >= end || *p != '"'
            || (p = id_string(sc, p, &esc)) == NULL)
            return NULL;
        p = id_ws(p, end);
        if (p >= end || *p != ':')
            return NULL;
        p = id_ws(p + 1, end);
    }
}

/* The value at p into *f if it is a string with no escape or null, state
 * 2 otherwise; the byte after it, or NULL. */
static const unsigned char *
id_take(id_scan *sc, const unsigned char *p, id_field *f)
{
    if (p < sc->end && *p == '"') {
        int esc;
        const unsigned char *q = id_string(sc, p, &esc);
        if (q != NULL) {
            f->state = esc ? 2 : 1;
            f->s = p + 1;
            f->n = q - p - 2;
        }
        return q;
    }
    if (p < sc->end && *p == 'n') {
        memset(f, 0, sizeof(*f));
        return id_literal(p, sc->end, "null");
    }
    f->state = 2;
    return id_skip(sc, p);
}

enum { ID_API, ID_KIND, ID_NAME, ID_NS, ID_META, ID_N };

static const unsigned char *id_metadata(id_scan *, const unsigned char *,
                                        id_field *);

/* The members of the object that opens at p ('{'): the top-level object
 * (apiVersion, kind, metadata) or, with `meta` set, the metadata object
 * (name, namespace).  The value of each such key, the key written without
 * an escape, is taken into its field; every other value is skipped.  A
 * key with an escape could spell any name: NULL.  Returns the byte after
 * the closing brace, or NULL. */
static const unsigned char *
id_object(id_scan *sc, const unsigned char *p, id_field *fields, int meta)
{
    static const char *const TOP[] = {"apiVersion", "kind", "metadata"};
    static const char *const META[] = {"name", "namespace"};
    const char *const *names = meta ? META : TOP;
    const int n = meta ? 2 : 3, first = meta ? ID_NAME : ID_API;
    const unsigned char *end = sc->end;
    p = id_ws(p + 1, end);
    if (p < end && *p == '}')
        return p + 1;
    for (;;) {
        int esc, hit = -1;
        if (p >= end || *p != '"')
            return NULL;
        const unsigned char *key = p + 1;
        if ((p = id_string(sc, p, &esc)) == NULL || esc)
            return NULL;
        size_t klen = (size_t)(p - key - 1);
        for (int i = 0; i < n; i++)
            if (strlen(names[i]) == klen && memcmp(key, names[i], klen) == 0)
                hit = i;
        p = id_ws(p, end);
        if (p >= end || *p != ':')
            return NULL;
        p = id_ws(p + 1, end);
        if (hit < 0)
            p = id_skip(sc, p);
        else if (!meta && hit == 2) /* "metadata" */
            p = id_metadata(sc, p, fields);
        else
            p = id_take(sc, p, &fields[first + hit]);
        if (p == NULL)
            return NULL;
        p = id_ws(p, end);
        if (p >= end)
            return NULL;
        if (*p == '}')
            return p + 1;
        if (*p != ',')
            return NULL;
        p = id_ws(p + 1, end);
    }
}

/* the top level's "metadata": an object gives name and namespace anew
 * (the last metadata wins whole), null gives neither, and anything else
 * is what `obj.get("metadata") or {}` has to answer for */
static const unsigned char *
id_metadata(id_scan *sc, const unsigned char *p, id_field *fields)
{
    memset(&fields[ID_NAME], 0, 3 * sizeof(*fields)); /* name, ns, meta */
    if (p < sc->end && *p == '{')
        return id_object(sc, p, fields, 1);
    if (p < sc->end && *p == 'n')
        return id_literal(p, sc->end, "null");
    fields[ID_META].state = 2;
    return id_skip(sc, p);
}

/* identity(obj): (apiVersion, kind, name, namespace) of an unloaded,
 * exact-class RawJSON read from its bytes, or None where the scan cannot
 * say exactly what the loaded dict would. */
static PyObject *
identity(PyObject *self, PyObject *obj)
{
    (void)self;
    if (raw_type == NULL || Py_TYPE(obj) != raw_type)
        Py_RETURN_NONE;
    PyObject *loaded = *(PyObject **)((char *)obj + off_loaded);
    PyObject *raw = *(PyObject **)((char *)obj + off_raw);
    if (loaded != Py_False || raw == NULL || !PyBytes_CheckExact(raw)
        || PyDict_GET_SIZE(obj) != 0)
        Py_RETURN_NONE;
    const unsigned char *p = (const unsigned char *)PyBytes_AS_STRING(raw);
    id_scan sc = {p + PyBytes_GET_SIZE(raw), 0};
    id_field fields[ID_N];
    memset(fields, 0, sizeof(fields));
    if (p >= sc.end || *p != '{'
        || (p = id_object(&sc, p, fields, 0)) == NULL
        || id_ws(p, sc.end) != sc.end)
        Py_RETURN_NONE;
    for (int i = 0; i < ID_N; i++)
        if (fields[i].state == 2)
            Py_RETURN_NONE;
    if (sc.high) {
        /* what json.loads would refuse to decode the fold must not name */
        PyObject *whole = PyUnicode_DecodeUTF8(
            PyBytes_AS_STRING(raw), PyBytes_GET_SIZE(raw), NULL);
        if (whole == NULL) {
            PyErr_Clear();
            Py_RETURN_NONE;
        }
        Py_DECREF(whole);
    }
    PyObject *out = PyTuple_New(4);
    if (out == NULL)
        return NULL;
    for (int i = 0; i < 4; i++) {
        PyObject *s = PyUnicode_DecodeUTF8(
            fields[i].n ? (const char *)fields[i].s : "", fields[i].n, NULL);
        if (s == NULL) {
            Py_DECREF(out);
            return NULL;
        }
        PyTuple_SET_ITEM(out, i, s);
    }
    return out;
}

/* track(obj): put an object that route() took off the collector's lists
 * back on them.  Nothing to do for one that is on them (tracking it twice
 * is an error) or whose type the collector does not know. */
static PyObject *
track(PyObject *self, PyObject *obj)
{
    (void)self;
    if (PyObject_IS_GC(obj) && !PyObject_GC_IsTracked(obj))
        PyObject_GC_Track(obj);
    Py_RETURN_NONE;
}

static PyMethodDef methods[] = {
    {"bind", bind, METH_O,
     "Take the RawJSON class whose instances the head scan reads."},
    {"head_kind", py_head_kind, METH_O,
     "The kind in the head of a document's bytes, or None."},
    {"route", route, METH_VARARGS,
     "Route listed objects into per-group chunk buffers until one fills."},
    {"identity", identity, METH_O,
     "(apiVersion, kind, name, namespace) of an unloaded RawJSON, or None."},
    {"track", track, METH_O,
     "Put an object back on the cyclic collector's lists."},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "gtpu_listroute", NULL, -1, methods,
    NULL, NULL, NULL, NULL,
};

PyMODINIT_FUNC
PyInit_gtpu_listroute(void)
{
    return PyModule_Create(&moduledef);
}
