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
